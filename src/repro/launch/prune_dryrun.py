"""Dry-run of the paper's technique itself on the production mesh.

Lowers the distributed SparseSwaps refinement step for LLAMA-3.1-8B's
largest layer (up-proj, W: 14336 x 4096 -> G: 4096x4096) on the 16x16
mesh, in three variants (§Perf cell C):

  dense    — paper-faithful: per-device dense ΔL (R_loc, d, d) per
             iteration (the straightforward GPU vectorization at TPU
             scale; R_loc = 56 rows/device).
  chunked  — our streaming search: ΔL materialized only per p-chunk
             (R_loc, d, chunk); same result bit-for-bit.
  gshard   — column-sharded G (d_in too big to replicate — demonstrates
             the granite-34b down-proj regime on this layer).

cost_analysis counts scan bodies once, so (like launch/dryrun.py) costs
are composed from two unrolled probes: cost(T) = base + T * per_iter.

    PYTHONPATH=src python -m repro.launch.prune_dryrun
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import dataclasses
import json
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import masks as masks_lib
from repro.core import swap_math as sm
from repro.launch import dryrun as dr
from repro.launch import mesh as mesh_lib

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun" / "prune_step"


def print_plan(arch: str = "llama31-8b", *,
               gram_budget_bytes: int = 256 << 20) -> None:
    """Render the full-model PrunePlan on the production mesh — shapes
    only (eval_shape params), zero FLOPs.

    The reduced Gram budget forces the down-proj (d_in=14336, 822 MB
    fp32 Gram) onto the column-sharded-G path, so the table shows both
    sharded regimes the variants below lower.
    """
    import repro.configs as configs
    import repro.models as models
    from repro import pruning

    cfg = configs.get(arch)
    api = models.build(cfg)
    abstract = jax.eval_shape(lambda: api.init(jax.random.key(0)))
    recipe = pruning.PruneRecipe(
        rules=(pruning.SiteRule("*.attn.*", pattern=masks_lib.NM(2, 4)),),
        pattern=masks_lib.PerRow(0.6))
    plan = pruning.plan_pruning(api, abstract, recipe,
                                mesh=mesh_lib.make_production_mesh(),
                                gram_budget_bytes=gram_budget_bytes)
    print(f"== {arch} pruning plan (production mesh, "
          f"G budget {gram_budget_bytes >> 20} MiB) ==")
    print(plan.describe())


def _refine_fn(mesh, pattern, *, t_max: int, variant: str, chunk: int = 512,
               unroll: bool = False):
    """(W, G, M0) -> (M, l0, l1); scan unrolled for the cost probes."""
    axes = tuple(mesh.axis_names)
    g_spec = P(None, axes) if variant == "gshard" else P(None, None)
    w_spec = P(None, None) if variant == "gshard" else P(axes, None)

    if variant in ("gshard", "2d"):
        from repro.pruning.distributed import refine_g_sharded
        kw = (dict(row_axes=("data",), col_axes=("model",))
              if variant == "2d" else {})

        def step(W, G, M0):
            return refine_g_sharded(W, G, M0, pattern, mesh, t_max=t_max,
                                    unroll=unroll, **kw)

        return step

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(w_spec, g_spec, w_spec),
             out_specs=(w_spec, P(axes), P(axes)),
             check_vma=False)
    def run(w, g, m0):
        c0 = sm.correlation_vector(w, m0, g)
        l0 = sm.row_loss(w, m0, g)

        def body(state, _):
            m, c, loss = state
            if variant == "dense":
                dl, u, p = sm.best_swap_dense(w, m, c, g)
            else:
                dl, u, p = sm.best_swap_chunked(w, m, c, g, chunk=chunk)
            m, c, acc = sm.apply_swap(w, m, c, g, dl, u, p)
            loss = jnp.where(acc, loss + dl, loss)
            return (m, c, loss), None

        (m, _, loss), _ = jax.lax.scan(body, (c0 * 0 + m0, c0, l0), None,
                                       length=t_max,
                                       unroll=True if unroll else 1)
        return m, l0, loss

    def step(W, G, M0):
        return run(W.astype(jnp.float32), G.astype(jnp.float32),
                   M0.astype(jnp.float32))

    return step


def lower_variant(variant: str, *, d_out=14336, d_in=4096, t_max=100,
                  chunk=512, probes=(2, 4)) -> dict:
    mesh = mesh_lib.make_production_mesh()
    n_dev = mesh.size
    pattern = masks_lib.PerRow(0.6)
    W = jax.ShapeDtypeStruct((d_out, d_in), jnp.float32)
    G = jax.ShapeDtypeStruct((d_in, d_in), jnp.float32)
    M = jax.ShapeDtypeStruct((d_out, d_in), jnp.float32)
    axes = tuple(mesh.axis_names)
    if variant == "gshard":
        w_spec, g_spec, l_spec = P(None, None), P(None, axes), P(None)
    elif variant == "2d":
        w_spec, g_spec, l_spec = P("data", None), P(None, "model"), P("data")
    else:
        w_spec, g_spec, l_spec = P(axes, None), P(None, None), P(axes)
    sh = lambda s: NamedSharding(mesh, s)
    in_sh = (sh(w_spec), sh(g_spec), sh(w_spec))
    out_sh = (sh(w_spec), sh(l_spec), sh(l_spec))

    out = {"variant": variant, "d_out": d_out, "d_in": d_in, "t_max": t_max,
           "chunk": chunk, "mesh": "16x16"}
    t0 = time.time()
    with mesh:
        # memory lowering (scan form, full t_max)
        fn = _refine_fn(mesh, pattern, t_max=t_max, variant=variant,
                        chunk=chunk)
        comp = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(
            W, G, M).compile()
        ma = comp.memory_analysis()
        out["arg_bytes"] = int(ma.argument_size_in_bytes)
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
        del comp

        # cost probes (unrolled): cost(T) = base + T * per_iter
        c = {}
        for T in probes:
            fnp = _refine_fn(mesh, pattern, t_max=T, variant=variant,
                             chunk=chunk, unroll=True)
            compp = jax.jit(fnp, in_shardings=in_sh,
                            out_shardings=out_sh).lower(W, G, M).compile()
            ca = compp.cost_analysis() or {}
            coll = dr.parse_collectives(compp.as_text(), n_dev, n_dev)
            c[T] = {"flops": float(ca.get("flops", 0)),
                    "bytes": float(ca.get("bytes accessed", 0)),
                    "ici": coll["ici"] + coll["dcn"]}
            del compp
    T1, T2 = probes

    def compose(key):
        per = (c[T2][key] - c[T1][key]) / (T2 - T1)
        return max(c[T1][key] - T1 * per + t_max * per, 0.0), per

    out["flops"], out["flops_per_iter"] = [x * n_dev for x in compose("flops")]
    out["bytes"], out["bytes_per_iter"] = [x * n_dev for x in compose("bytes")]
    out["coll"], out["coll_per_iter"] = [x * n_dev for x in compose("ici")]
    out["compile_s"] = time.time() - t0
    out["roofline"] = {
        "compute_s": out["flops"] / (n_dev * dr.PEAK_FLOPS),
        "memory_s": out["bytes"] / (n_dev * dr.HBM_BW),
        "ici_s": out["coll"] / (n_dev * dr.ICI_BW),
    }
    rf = out["roofline"]
    rf["dominant"] = max(rf, key=lambda k: rf[k] if k.endswith("_s") else -1)
    return out


def main(variants=("dense", "chunked", "gshard")):
    RESULTS.mkdir(parents=True, exist_ok=True)
    print_plan()
    rows = []
    for v in variants:
        try:
            r = lower_variant(v)
        except Exception as e:  # noqa: BLE001
            r = {"variant": v, "error": f"{type(e).__name__}: {e}"}
        rows.append(r)
        (RESULTS / f"{v}.json").write_text(json.dumps(r, indent=1))
        if "error" in r:
            print(f"[FAIL] {v}: {r['error'][:200]}")
        else:
            rf = r["roofline"]
            print(f"[ok ] {v:8s} mem/dev={(r['arg_bytes']+r['temp_bytes'])/2**30:6.2f}GiB "
                  f"compute={rf['compute_s']:8.4f}s memory={rf['memory_s']:8.4f}s "
                  f"ici={rf['ici_s']:8.4f}s dom={rf['dominant']}")
    return rows


if __name__ == "__main__":
    main(sys.argv[1:] or ("dense", "chunked", "gshard"))
