"""Shared model building blocks (pure JAX, no flax).

Conventions:
* params are nested dicts of jnp arrays; linear weights are (d_out, d_in)
  — the paper's orientation, so pruning masks apply as ``(M ⊙ W)``.
* every prunable linear goes through ``dense`` which (a) applies an
  optional pruning mask and (b) optionally emits a Gram-tap contribution
  ``xᵀx`` for calibration (paper §2.1.2). Taps are returned functionally
  and stack across scan-over-layers.
* compute dtype is configurable (bf16 on TPU); Gram taps & norms are fp32.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.packed import PackedWeight

Params = dict
# name -> {"g": (d_in, d_in) gram, "s": (d_in,) feature sums, "n": () count}
# g feeds SparseSwaps/Wanda/RIA/SparseGPT; s/n give DSnoT its feature
# means/variances (mu = s/n, E[x^2] = diag(g)/n) from the same single pass.
# Under a reduced TapPolicy an entry may carry "d" (the (d_in,) diagonal
# Σx² per feature) instead of the full "g" — see repro.pruning.stats.
Taps = dict


# ---------------------------------------------------------------------------
# tap emission policy (pluggable accumulator)
# ---------------------------------------------------------------------------

class TapPolicy:
    """Decides what calibration statistics a tap site emits, and how.

    ``dense`` (and the MoE block) route every tap through the active
    policy instead of hard-coding the full {g, s, n} entry, so the same
    model code serves both the legacy dict path and the recipe-aware
    streaming path (``repro.pruning.stats``):

    * ``fields(name)`` — which statistics the tap named ``name`` emits:
      any subset of ``("g", "d", "s", "n")`` where ``g`` is the full
      (d, d) Gram contribution, ``d`` its diagonal only (Σx² per
      feature), ``s`` the feature sums and ``n`` the token count.
      An empty tuple skips the tap entirely (no state, no FLOPs).
    * ``gram(x2)`` — the XᵀX kernel for a flattened (tokens, d) chunk;
      overridden to the Pallas ``kernels.ops.gram_xtx`` on TPU.
    * ``gram_experts(x5)`` — the MoE capacity-buffer variant,
      (B, groups, E, cap, d) -> (E, d, d).

    Policies are consulted at *trace* time, so a jitted calibration step
    bakes its policy in; install one with ``use_tap_policy`` around the
    trace (re-jit per policy).
    """

    def fields(self, name: str) -> tuple[str, ...]:
        return ("g", "s", "n")

    def gram(self, x2: jnp.ndarray) -> jnp.ndarray:
        return x2.T @ x2

    def gram_experts(self, x5: jnp.ndarray) -> jnp.ndarray:
        return jnp.einsum("bneci,bnecj->eij", x5, x5)


DEFAULT_TAP_POLICY = TapPolicy()
_tap_policy: TapPolicy = DEFAULT_TAP_POLICY


def tap_policy() -> TapPolicy:
    """The policy currently governing tap emission."""
    return _tap_policy


@contextlib.contextmanager
def use_tap_policy(policy: TapPolicy):
    """Install ``policy`` for the dynamic (trace-time) extent of the block."""
    global _tap_policy
    prev = _tap_policy
    _tap_policy = policy
    try:
        yield
    finally:
        _tap_policy = prev


def emit_tap(taps: Taps, name: str, x: jnp.ndarray) -> None:
    """Accumulate ``x``'s calibration statistics into ``taps[name]``.

    The single emission hook for every standard (non-MoE) prunable
    linear: builds the entry the active policy asks for and tree-adds it
    into the dict (created on first use). A policy returning no fields
    leaves the dict untouched — the tap never materializes.
    """
    pol = _tap_policy
    fields = pol.fields(name)
    if not fields:
        return
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    ent = {}
    if "g" in fields:
        ent["g"] = pol.gram(x2)
    if "d" in fields:
        ent["d"] = jnp.sum(x2 * x2, axis=0)
    if "s" in fields:
        ent["s"] = jnp.sum(x2, axis=0)
    if "n" in fields:
        ent["n"] = jnp.float32(x2.shape[0])
    prev = taps.get(name)
    taps[name] = ent if prev is None else jax.tree.map(jnp.add, prev, ent)


def zero_tap_entry(name: str, d: int) -> dict:
    """The all-zero entry ``emit_tap`` would produce for a (·, d) input.

    Models that emit taps conditionally (zamba's shared block behind a
    ``lax.cond``) use this to build the structurally-matching zero branch
    under whatever policy is active; ``{}`` means the tap is disabled.
    """
    pol = _tap_policy
    fields = pol.fields(name)
    ent = {}
    if "g" in fields:
        ent["g"] = jnp.zeros((d, d), jnp.float32)
    if "d" in fields:
        ent["d"] = jnp.zeros((d,), jnp.float32)
    if "s" in fields:
        ent["s"] = jnp.zeros((d,), jnp.float32)
    if "n" in fields:
        ent["n"] = jnp.float32(0.0)
    return ent


# ---------------------------------------------------------------------------
# matmul policy (pluggable serving execution path)
# ---------------------------------------------------------------------------

def apply_epilogue(y: jnp.ndarray, bias=None,
                   act: str | None = None) -> jnp.ndarray:
    """``act(y + bias)`` — the reference (unfused) matmul epilogue.

    ``act`` keys come from ``kernels.spmm.EPILOGUES`` (a superset of
    ``ACTS``); the fused packed kernels compute exactly this on their
    fp32 accumulator.
    """
    from repro.kernels.spmm import EPILOGUES
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if act is not None:
        y = EPILOGUES[act](y)
    return y

class MatmulPolicy:
    """Decides how a prunable linear *executes*, mirroring ``TapPolicy``.

    ``dense`` (and the MoE expert einsums) route every weight
    application through the active policy instead of hard-coding
    ``x @ (mask ⊙ w)ᵀ``, so the same model code serves three regimes
    without per-model changes:

    * dense / masked-dense — the default below (training, calibration,
      reference serving);
    * packed — when a param leaf is a ``core.packed.PackedWeight`` the
      policy's ``packed_matmul`` runs it through the sparse kernels
      (``kernels.spmm``); ``kernel`` selects pallas/jnp (``"auto"`` =
      Pallas on TPU, the phase-aware jnp fallback elsewhere).

    Every path takes an optional fused epilogue — ``bias`` (a (d_out,)
    array or None) and ``act`` (a ``kernels.spmm.EPILOGUES`` key or
    None), applied as ``act(y + bias)``. On the packed Pallas path the
    epilogue runs in-kernel on the fp32 accumulator, so the
    pre-activation never round-trips through HBM between the spmm and
    the nonlinearity; the dense path applies it inline (XLA fuses it).
    ``fuse_epilogue = False`` turns the knob off: ``dense`` then applies
    the identical ``act(y + bias)`` *outside* the policy — the unfused
    reference the parity tests compare against.

    Policies are consulted at *trace* time (install with
    ``use_matmul_policy`` around the jit; re-jit per policy), exactly
    like tap policies.
    """

    kernel: str = "auto"
    fuse_epilogue: bool = True
    mesh = None     # the mesh packed leaves are row-sharded on (or None)

    def matmul(self, x: jnp.ndarray, w: jnp.ndarray,
               mask: jnp.ndarray | None, *, bias=None,
               act: str | None = None) -> jnp.ndarray:
        if mask is not None:
            w = w * mask.astype(w.dtype)
        return apply_epilogue(x @ w.T.astype(x.dtype), bias, act)

    def packed_matmul(self, x: jnp.ndarray, pw: PackedWeight, *,
                      bias=None, act: str | None = None) -> jnp.ndarray:
        from repro.kernels import spmm
        return spmm.spmm(x, pw, kernel=self.kernel, bias=bias, act=act,
                         mesh=self.mesh)

    def packed_matmul_stacked(self, x: jnp.ndarray, pw: PackedWeight, *,
                              bias=None, act: str | None = None
                              ) -> jnp.ndarray:
        """Per-instance variant for stacked leaves (MoE experts)."""
        from repro.kernels import spmm
        return spmm.spmm_stacked(x, pw, kernel=self.kernel, bias=bias,
                                 act=act, mesh=self.mesh)


class PackedMatmulPolicy(MatmulPolicy):
    """A ``MatmulPolicy`` with an explicit kernel/epilogue choice."""

    def __init__(self, kernel: str = "auto", fuse_epilogue: bool = True,
                 mesh=None):
        self.kernel = kernel
        self.fuse_epilogue = fuse_epilogue
        self.mesh = mesh


DEFAULT_MATMUL_POLICY = MatmulPolicy()
_matmul_policy: MatmulPolicy = DEFAULT_MATMUL_POLICY


def matmul_policy() -> MatmulPolicy:
    """The policy currently governing prunable-linear execution."""
    return _matmul_policy


@contextlib.contextmanager
def use_matmul_policy(policy: MatmulPolicy):
    """Install ``policy`` for the dynamic (trace-time) extent of the block."""
    global _matmul_policy
    prev = _matmul_policy
    _matmul_policy = policy
    try:
        yield
    finally:
        _matmul_policy = prev


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def normal_init(key, shape, scale: float, dtype) -> jnp.ndarray:
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def linear_init(key, d_out: int, d_in: int, dtype) -> jnp.ndarray:
    return normal_init(key, (d_out, d_in), d_in**-0.5, dtype)


# ---------------------------------------------------------------------------
# dense layer with mask + gram tap
# ---------------------------------------------------------------------------

def dense(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    mask: jnp.ndarray | None = None,
    tap: str | None = None,
    taps: Taps | None = None,
    bias: jnp.ndarray | None = None,
    act: str | None = None,
) -> jnp.ndarray:
    """y = act(x @ ((mask ⊙ w)ᵀ) + bias). x: (..., d_in), w: (d_out, d_in).

    When ``taps`` is a dict and ``tap`` a name, accumulates the
    statistics the active ``TapPolicy`` selects for x into taps[tap]
    (created on first use; may be skipped entirely by the policy).

    Execution is delegated to the active ``MatmulPolicy``: a
    ``PackedWeight`` leaf (serving a packed sparse export) dispatches to
    the spmm kernels — ``mask`` must then be ``None``, the mask is baked
    into the packing. ``bias``/``act`` are the fused epilogue — handed
    to the policy when it fuses (in-kernel on the packed Pallas path),
    applied here as the identical ``act(y + bias)`` when it doesn't.
    """
    if taps is not None and tap is not None:
        emit_tap(taps, tap, x)
    pol = _matmul_policy
    fused = pol.fuse_epilogue
    eb, ea = (bias, act) if fused else (None, None)
    if isinstance(w, PackedWeight):
        if mask is not None:
            raise ValueError("PackedWeight already encodes its mask; "
                             "serve packed params with masks=None")
        y = pol.packed_matmul(x, w, bias=eb, act=ea)
    else:
        y = pol.matmul(x, w, mask, bias=eb, act=ea)
    return y if fused else apply_epilogue(y, bias, act)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def relu2(x):
    r = jax.nn.relu(x)
    return r * r


ACTS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": relu2,
    "relu": jax.nn.relu,
}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(d_rot: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, *, pct: float = 1.0,
               theta: float = 10000.0) -> jnp.ndarray:
    """Rotary embedding on the leading ``pct`` fraction of the head dim.

    x: (B, S, H, Dh); positions: (B, S). pct<1 gives partial rotary
    (chatglm-style 2d RoPE applies rotation to half the dims).
    """
    dh = x.shape[-1]
    d_rot = int(dh * pct) // 2 * 2
    if d_rot == 0:
        return x
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    freqs = rope_freqs(d_rot, theta)                        # (d_rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d_rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1 = xr[..., 0::2].astype(jnp.float32)
    x2 = xr[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x1 * sin + x2 * cos
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1)


# ---------------------------------------------------------------------------
# scan wrapper
# ---------------------------------------------------------------------------

def scan(body, init, xs, *, cfg=None, length=None):
    """``lax.scan`` honoring cfg.scan_layers.

    scan_layers=True (default) keeps the compact while-loop HLO (fast
    compile, low code size). scan_layers=False fully unrolls — the dry-run
    cost lowering uses this because XLA's HloCostAnalysis counts a while
    body ONCE regardless of trip count (verified empirically), so only the
    unrolled program yields exact FLOP/byte/collective totals.
    """
    unroll = True if (cfg is not None and not cfg.scan_layers) else 1
    return jax.lax.scan(body, init, xs, length=length, unroll=unroll)


# ---------------------------------------------------------------------------
# cache page views (serving)
# ---------------------------------------------------------------------------

def rows_to_pages(x: jnp.ndarray, page: int, axis: int) -> jnp.ndarray:
    """View a cache's sequence axis as (n_pages, page) — zero-copy reshape.

    The bridge between the models' dense decode caches (contiguous
    sequence rows) and ``serve.kvcache``'s paged pool: a slot row
    (L, C, kvH, dh) with ``axis=1`` becomes (L, C/page, page, kvH, dh),
    ready to scatter page-by-page. ``C`` must divide by ``page``.
    """
    s = x.shape[axis]
    if s % page:
        raise ValueError(f"sequence dim {s} not divisible by page {page}")
    return x.reshape(*x.shape[:axis], s // page, page, *x.shape[axis + 1:])


def pages_to_rows(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inverse of ``rows_to_pages``: merge (n_pages, page) back into one
    contiguous sequence axis at ``axis``."""
    n, p = x.shape[axis], x.shape[axis + 1]
    return x.reshape(*x.shape[:axis], n * p, *x.shape[axis + 2:])


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def merge_taps(dst: Taps, src: Taps, prefix: str = "") -> Taps:
    for k, v in src.items():
        key = f"{prefix}{k}"
        dst[key] = dst.get(key, 0.0) + v
    return dst


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_ff: int               # per-expert hidden size
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 64
