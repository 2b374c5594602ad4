"""Train/serve step functions — the units the launcher jits and shards.

``train_step`` is a pure function (state, batch) -> (state, metrics); the
masked variant keeps a pruning mask invariant through the update (sparse
finetuning). ``make_serve_steps`` builds prefill/decode closures. These
are what ``launch/dryrun.py`` lowers on the production mesh.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import ModelApi
from repro.optim import adamw


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState


def init_state(api: ModelApi, key) -> TrainState:
    params = api.init(key)
    return TrainState(params=params, opt=adamw.init(params))


def train_step_fn(api: ModelApi, opt_cfg: adamw.AdamWConfig, *, masks=None):
    """The raw (unjitted) train step — what the dry-run lowers on the
    production mesh and ``make_train_step`` jits locally.

    cfg.grad_accum > 1 splits the batch into microbatches scanned
    sequentially with fp32 grad accumulation: live activation memory
    scales ~1/k (the §Perf cell-A memory lever) at the cost of k-times
    gradient-reduction traffic.
    """
    accum = max(api.cfg.grad_accum, 1)

    def grad_fn(params, batch):
        def loss_fn(p):
            loss, aux = api.loss(p, batch, masks=masks)
            return loss, aux

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        if accum == 1:
            (loss, aux), grads = grad_fn(state.params, batch)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
                batch)

            def body(acc, b):
                (l, aux), g = grad_fn(state.params, b)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), acc, g)
                return acc, (l, aux)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            from repro.models import common as _common
            # the FULL aux tree rides through the scan — the accum path
            # must report the same metric dict as the accum == 1 path
            grads, (losses, auxes) = _common.scan(body, zeros, mb,
                                                  cfg=api.cfg)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = jnp.mean(losses)
            aux = jax.tree.map(lambda x: jnp.mean(x, axis=0), auxes)
        new_params, new_opt, om = adamw.update(
            opt_cfg, grads, state.opt, state.params, masks=masks)
        metrics = {"loss": loss,
                   **{k: v for k, v in aux.items() if k != "taps"}, **om}
        return TrainState(new_params, new_opt), metrics

    return step


def make_train_step(api: ModelApi, opt_cfg: adamw.AdamWConfig, *,
                    masks=None, donate: bool = True):
    """Build the jitted train step. ``masks`` (optional) is closed over —
    it is part of the compiled program, matching how a sparse-finetune job
    would deploy (masks are static artifacts, not per-step inputs)."""
    step = train_step_fn(api, opt_cfg, masks=masks)
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def prefill_step_fn(api: ModelApi, *, masks=None):
    def step(params, batch, cache):
        return api.prefill(params, batch, cache, masks=masks)

    return step


def decode_step_fn(api: ModelApi, *, masks=None):
    def step(params, token, cache):
        return api.decode_step(params, token, cache, masks=masks)

    return step


def make_eval_step(api: ModelApi, *, masks=None):
    """jit'd (params, batch) -> (mean CE, valid-token count).

    ``masks`` enter the program as an argument: a closed-over array is
    baked into the executable as a constant, which for a model's masks
    means gigabytes of constants to fold at compile time.
    """

    @jax.jit
    def step(params, batch, masks):
        loss, aux = api.loss(params, batch, masks=masks)
        n_valid = jnp.sum((batch["labels"] >= 0).astype(jnp.float32))
        return aux["ce"], n_valid

    return partial(step, masks=masks)


def perplexity(api: ModelApi, params, batches, *, masks=None) -> float:
    """Token-weighted mean-CE perplexity over an iterable of batches.

    Each batch's mean CE (already normalized over its own valid tokens)
    is weighted by that batch's valid-token count, so ragged final
    batches or padded prompts don't bias the estimate the way an
    unweighted mean of per-batch means would.
    """
    step = make_eval_step(api, masks=masks)
    tot, n = 0.0, 0.0
    for b in batches:
        ce, cnt = step(params, b)
        tot += float(ce) * float(cnt)
        n += float(cnt)
    return float(jnp.exp(tot / max(n, 1.0)))


def make_serve_steps(api: ModelApi, *, masks=None):
    """jit'd prefill (params, batch, cache) and decode (params, token,
    cache); ``masks`` are bound as an argument, never a closure constant
    (see ``make_eval_step``)."""
    prefill = jax.jit(lambda p, b, c, m: api.prefill(p, b, c, masks=m))
    decode = jax.jit(lambda p, t, c, m: api.decode_step(p, t, c, masks=m))
    return partial(prefill, m=masks), partial(decode, m=masks)


def greedy_decode(api: ModelApi, params, prompt, n_new: int, *, masks=None):
    """Serve a batch of prompts: prefill + n_new greedy decode steps."""
    B, S = prompt["tokens"].shape
    cache = api.init_cache(params, B, S + n_new)
    prefill, decode = make_serve_steps(api, masks=masks)
    logits, cache = prefill(params, prompt, cache)
    toks = [jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)]
    for _ in range(n_new - 1):
        logits, cache = decode(params, toks[-1][:, None], cache)
        toks.append(jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32))
    return jnp.stack(toks, axis=1)          # (B, n_new)
