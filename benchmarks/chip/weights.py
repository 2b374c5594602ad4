"""Seeded inputs: model weights and token ids, made on the device.

The benchmark, not the program, makes every input, so the reference can
take the same values without touching anything the program produced.
Weights come from one jitted call over the program's parameter layout
(its shapes and dtypes from ``jax.eval_shape``), in the served dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# stream tags, so weights, calibration tokens and prompts never share keys
WEIGHTS, CALIB, PROMPTS = 1, 2, 3


def base_key(seed: int, stream: int):
    """A key from a seed of any size (``--seed`` may exceed 32 bits)."""
    seed = int(seed)
    k = jax.random.key(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def _leaf_init(key, path: tuple[str, ...], shape, dtype):
    name = path[-1]
    f32 = jnp.float32
    if name == "scale":                       # norm gains, near 1
        x = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif name in ("bq", "bk", "bv", "bias"):
        x = 0.02 * jax.random.normal(key, shape, f32)
    elif name in ("embed", "head"):
        x = 0.02 * jax.random.normal(key, shape, f32)
    elif len(shape) >= 2:                     # (..., d_out, d_in) linears
        x = jax.random.normal(key, shape, f32) * shape[-1] ** -0.5
    else:
        x = jnp.zeros(shape, f32)
    return x.astype(dtype)


def make_params(init_fn, seed: int):
    """Random weights in the layout ``init_fn(key)`` returns, one jit."""
    shapes = jax.eval_shape(init_fn, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(str(getattr(p, "key", p)) for p in path)
             for path, _ in flat]

    @jax.jit
    def make(key):
        leaves = [_leaf_init(jax.random.fold_in(key, i), path, s.shape,
                             s.dtype)
                  for i, (path, (_, s)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(make(base_key(seed, WEIGHTS)))


def token_ids(seed: int, stream: int, index: int, shape, vocab: int):
    """Uniform token ids of ``shape`` for draw ``index`` of a stream."""
    key = jax.random.fold_in(base_key(seed, stream), index)
    return jax.random.randint(key, shape, 0, vocab, jnp.int32)
