"""Runner for serving mixes: closed-loop clients on the continuous scheduler.

Set-up makes the weights from the seed, prunes every linear site to the
mix's per-row sparsity by magnitude (speed depends on the pattern only,
so no refinement runs), builds the program's ``ServeEngine`` in the
mix's format, and warms every program the traffic can reach: each
prefill bucket of its prompt lengths, every decode-chunk program, and
the scheduler's join, leave and compaction paths.

Traffic: ``requests`` prompt and output lengths, each a set of
log-normal quantiles with the mix's mean and sigma clipped to its range.
Every seed serves the same two sets, each in an order drawn from the
seed, and the seed draws the token ids. Each client sends its next
request as soon as its last one completes. The window opens when every
client's first request has been admitted and closes at the first step
boundary after ``--seconds``.

After the window a seeded sample of finished requests, the longest
among them, is run through the plain float32 reference: at every served
position the gap is how far the served token's reference logit lies
below the reference's best (0 where greedy decoding agrees), and
``mean_gap``, their mean over the sample, is compared. (The widest gap
is reported too; it is bounded by bfloat16 rounding for the program and
by float8 rounding for the control, and over a few hundred tokens the
two overlap, see PERF.md.)
"""
from __future__ import annotations

import collections
import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import weights as weights_lib

# limit on the mean gap of served tokens below the reference's best
# logit; PERF.md gives the readings it was set from
LIMITS = {"mean_gap": 0.004}

POSITIONS = 128     # served positions scored per reference call


def magnitude_masks(params, sparsity: float, prunable) -> dict:
    """Keep the largest |w| of each row of every prunable layer linear
    (``params["layers"][group][name]``, name in ``prunable``), exactly
    the pattern's count."""
    @jax.jit
    def one(w):
        d = w.shape[-1]
        keep = d - int(round(sparsity * d))
        _, idx = jax.lax.top_k(jnp.abs(w.astype(jnp.float32)), keep)
        m = jnp.zeros(w.shape, jnp.float32)
        return jnp.put_along_axis(m, idx, 1.0, axis=-1, inplace=False)

    return {"layers": {group: {name: one(w) for name, w in leaves.items()
                               if name in prunable}
                       for group, leaves in params["layers"].items()
                       if set(prunable) & set(leaves)}}


def _lengths(spec: dict, n: int) -> np.ndarray:
    """n log-normal quantiles with the spec's mean and sigma, clipped."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
    mu = np.log(spec["mean"]) - spec["sigma"] ** 2 / 2
    v = np.exp(mu + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def requests(seed: int, mix: dict, vocab: int) -> list[tuple]:
    """[(prompt ids, max_new)] in the order clients take them.

    Every seed serves the same set of prompt lengths and the same set of
    output lengths, each in an order drawn from the seed; the seed also
    draws the token ids.
    """
    n = mix["requests"]
    p, o = _lengths(mix["prompt_len"], n), _lengths(mix["output_len"], n)
    rng = np.random.default_rng([seed, weights_lib.PROMPTS])
    p, o = p[rng.permutation(n)], o[rng.permutation(n)]
    return [(rng.integers(0, vocab, int(pi), dtype=np.int32), int(oi))
            for pi, oi in zip(p, o)]


def warm(engine, kw: dict, reqs: list) -> None:
    """Compile every program the traffic reaches, then drop the state."""
    from repro.serve import ContinuousScheduler
    from repro.serve.engine import next_pow2
    sched = ContinuousScheduler(engine, **kw)
    sched.warm()
    page, cap = kw["page_size"], kw["capacity"]
    buckets = sorted({min(max(page, next_pow2(len(p))), cap)
                      for p, _ in reqs})
    for i, b in enumerate(buckets):
        sched.submit(np.zeros(min(b, cap - 2 - i), np.int32), 2 + i)
    sched.run_until_idle()


def run(run: harness.Run) -> None:
    from repro.serve import ContinuousScheduler, ServeEngine

    mix, seed = run.mix, run.seed
    cfg, api = harness.program_model(run.config)
    ref = harness.reference(run.config)
    params = weights_lib.make_params(api.init, seed)
    masks = jax.block_until_ready(magnitude_masks(
        params, mix["mask"]["sparsity"], ref.SITE_INPUT))
    run.note("weights and masks")
    engine = ServeEngine(api, params, masks=masks, fmt=mix["format"])
    kw = dict(mix["scheduler"])
    reqs = requests(seed, mix, cfg.vocab_size)
    run.note("engine")
    warm(engine, kw, reqs)
    run.note("warm-up")

    sched = ContinuousScheduler(engine, **kw)
    info: dict = {}            # rid -> (request index, prompt length)
    arrivals: dict = collections.defaultdict(list)
    sched_tokens: dict = collections.defaultdict(list)   # rid -> tokens
    done: dict = {}
    nxt = 0

    def send():
        nonlocal nxt
        prompt, max_new = reqs[nxt % len(reqs)]
        rid = sched.submit(prompt, max_new)
        info[rid] = (nxt % len(reqs), len(prompt))
        nxt += 1

    for _ in range(mix["clients"]):
        send()
    waiting = set(info)         # first requests not yet admitted
    t_open = None
    while True:
        with run.span("step"):
            ev = sched.step()
        t = time.perf_counter()
        for rid, toks in ev.tokens.items():
            arrivals[rid].extend([t] * len(toks))
            sched_tokens[rid].extend(toks)
        for c in ev.completed:
            done[c.rid] = c
            if t_open is None or t - t_open < run.seconds:
                send()
        if t_open is None:
            waiting -= set(ev.prefilled)
            if not waiting:
                run.note("first admissions")
                t_open = run.open_window()
        elif t - t_open >= run.seconds:
            break
    run.close_window()
    run.read_memory_peak()

    w0, w1 = run.window
    tokens = 0
    gap_list = []
    prefill_in, decode_pos = [], []
    for rid, ts in arrivals.items():
        P = info[rid][1]
        for k, tk in enumerate(ts):
            if not (w0 < tk <= w1):
                continue
            tokens += 1
            if k == 0:
                prefill_in.append(P)
            else:
                gap_list.append(tk - ts[k - 1])
                decode_pos.append(P + k - 1)
    run.attempted = len(info)
    run.facts.update(
        tokens=tokens, itl=gap_list, prefill_prompts=prefill_in,
        decode_positions=decode_pos)
    run.facts["kept_params"] = kept_params(masks)

    served = {r: list(c.tokens) for r, c in done.items()}
    sample = choose(served, info, seed, mix["check_tokens"])
    if sum(len(served[r]) for r in sample) < mix["check_tokens"]:
        # too few finished: what in-flight requests were served so far
        # counts too
        served = dict(sched_tokens)
        sample = choose(served, info, seed, mix["check_tokens"])
    m = ref.model_fields(run.config)
    del sched, engine
    gc.collect()    # free the engine's device buffers before the reference
    compare(run, ref, m, params, masks,
            [(reqs[info[r][0]][0], np.asarray(served[r], np.int32))
             for r in sample])


def choose(done: dict, info: dict, seed: int, want: int) -> list:
    """The request with the longest sequence, then seeded others until
    ``want`` served tokens (``done`` maps rid -> served tokens)."""
    rids = sorted(done)
    if not rids:
        return []
    longest = max(rids, key=lambda r: info[r][1] + len(done[r]))
    rng = np.random.default_rng([seed, 17])
    out, n = [longest], len(done[longest])
    for r in rng.permutation([r for r in rids if r != longest]):
        if n >= want:
            break
        out.append(int(r))
        n += len(done[int(r)])
    return out


def compare(run, ref, m: dict, params, masks, sample) -> None:
    """How far served tokens' reference logits lie below the best one,
    ``POSITIONS`` served positions to a reference call."""
    key = tuple(sorted(m.items()))
    gaps, low = [], []
    for prompt, out in sample:
        toks = np.concatenate([prompt, out]).astype(np.int32)
        P = len(prompt)
        S = 1 << max(int(len(toks)) - 1, 0).bit_length()
        seq = np.zeros((1, S), np.int32)
        seq[0, :len(toks)] = toks
        for b in range(0, len(out), POSITIONS):
            blk = out[b:b + POSITIONS]
            n = len(blk)
            pos = np.full((POSITIONS,), P - 1, np.int32)
            pos[:n] = np.arange(P - 1 + b, P - 1 + b + n)
            tgt = np.zeros((POSITIONS,), np.int32)
            tgt[:n] = blk
            args = (params, masks, jnp.asarray(seq), jnp.asarray(pos))
            gaps.extend(np.asarray(ref.served_gaps(
                *args, jnp.asarray(tgt), m=key))[:n].tolist())
            if run.control:
                low.extend(np.asarray(ref.control_gaps(*args, m=key))[:n]
                           .tolist())
    run.facts.update(checked_tokens=len(gaps), widest_gap=max(gaps))
    run.note(f"served tokens checked {len(gaps)}, widest gap {max(gaps)!r}")
    run.check("mean_gap", float(np.mean(gaps)), LIMITS["mean_gap"])
    if run.control:
        # the control in the program's place, judged by the same check
        run.control_run = run.shadow()
        run.control_run.check("mean_gap", float(np.mean(low)),
                              LIMITS["mean_gap"])
        run.control_run.facts["widest_gap"] = max(low)


def kept_params(masks) -> int:
    """Kept weights of all layers (what a token must multiply)."""
    return int(sum(int(jnp.sum(x)) for x in jax.tree.leaves(masks)))
