"""Plain float32 reference of a pre-norm decoder-only transformer.

Independent of the program: straightforward ``jax.numpy`` at highest
matmul precision, no kernels, no cache, no batching tricks. It follows
the configuration file's fields:

  x = embed[tokens]
  per layer:  h = rmsnorm(x) · scale1
              q, k, v = h Wqᵀ (+ bq), h Wkᵀ (+ bk), h Wvᵀ (+ bv)
              rotary on the leading ``rope_pct`` of each head, pairs
              (2i, 2i+1), frequency theta^(-2i / d_rot)
              causal softmax(q kᵀ / sqrt(dh)) v, query head j reading
              kv head j // (heads / kv_heads)
              x = x + o Woᵀ
              h = rmsnorm(x) · scale2
              plain: u = act(h Wupᵀ)
              gated: u = act(h Wgateᵀ) ⊙ (h Wupᵀ)
              x = x + u Wdownᵀ
  logits = rmsnorm(x) · scale_f  Headᵀ

rmsnorm uses eps 1e-5; act is relu² or silu. Masks multiply their weight
elementwise. Weights are read by name from the parameter tree the
benchmark made (``layers.attn.wq`` ...), stacked on a leading layer axis.

``quant="fp8"`` is the control: every linear's operands (and the Gram
inputs) are rounded to float8 e4m3 with a per-tensor scale for weights
and a per-row scale for activations, then multiplied exactly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
_E4M3_MAX = 448.0

# which reference input feeds each prunable site (q/k/v share one input,
# gate/up another)
SITE_INPUT = {"wq": "attn_in", "wk": "attn_in", "wv": "attn_in",
              "wo": "wo_in", "w_gate": "mlp_in", "w_up": "mlp_in",
              "w_down": "down_in"}


def _fp8(x, axis):
    """Round to e4m3 with a scale per slice along ``axis`` (None: tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / _E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _act_q(x, quant):
    return _fp8(x, -1) if quant == "fp8" else x


def mm(x, w, quant=None):
    """x (..., d_in) @ w (d_out, d_in)ᵀ in float32 at highest precision."""
    x, w = x.astype(F32), w.astype(F32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST)


def rmsnorm(x, scale):
    x = x.astype(F32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-5) * scale.astype(F32)


def _act(name, x):
    if name == "relu2":
        r = jnp.maximum(x, 0.0)
        return r * r
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    raise ValueError(f"reference has no activation {name!r}")


def rope(x, pos, pct, theta):
    """x (B, S, H, dh); pos (S,)."""
    dh = x.shape[-1]
    d_rot = int(dh * pct) // 2 * 2
    if d_rot == 0:
        return x
    freqs = 1.0 / theta ** (jnp.arange(0, d_rot, 2, dtype=F32) / d_rot)
    ang = pos.astype(F32)[:, None] * freqs[None, :]          # (S, d_rot/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0:d_rot:2], x[..., 1:d_rot:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([rot.reshape(*x.shape[:-1], d_rot),
                            x[..., d_rot:]], axis=-1)


def _w(lp, group, name, masks, i):
    w = lp[group][name][i].astype(F32)
    if masks is not None:
        w = w * masks[group][name][i].astype(F32)
    return w


def layer(lp, i, x, m, *, quant=None, masks=None, record=None):
    """Layer ``i`` of the stacked tree ``lp`` on x (B, S, d) float32."""
    B, S, _ = x.shape
    H, kvH = m["n_heads"], m["n_kv_heads"]
    dh = m["d_model"] // H
    pos = jnp.arange(S)
    at = lp["attn"]
    h = rmsnorm(x, lp["ln1"]["scale"][i])
    if record is not None:
        record("attn_in", h)

    def proj(name, bias):
        y = mm(h, _w(lp, "attn", name, masks, i), quant)
        return y + at[bias][i].astype(F32) if bias in at else y

    q = proj("wq", "bq").reshape(B, S, H, dh)
    k = proj("wk", "bk").reshape(B, S, kvH, dh)
    v = proj("wv", "bv").reshape(B, S, kvH, dh)
    q = rope(q, pos, m["rope_pct"], m["rope_theta"])
    k = rope(k, pos, m["rope_pct"], m["rope_theta"])
    rep = H // kvH
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * dh ** -0.5
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    o = o.reshape(B, S, H * dh)
    if record is not None:
        record("wo_in", o)
    x = x + mm(o, _w(lp, "attn", "wo", masks, i), quant)
    h = rmsnorm(x, lp["ln2"]["scale"][i])
    if record is not None:
        record("mlp_in", h)
    up = mm(h, _w(lp, "mlp", "w_up", masks, i), quant)
    if m["mlp"] == "gated":
        gate = mm(h, _w(lp, "mlp", "w_gate", masks, i), quant)
        u = _act(m["act"], gate) * up
    else:
        u = _act(m["act"], up)
    if record is not None:
        record("down_in", u)
    return x + mm(u, _w(lp, "mlp", "w_down", masks, i), quant)


def gram_shapes(m) -> dict:
    """Reference Gram shapes per input kind, stacked over layers."""
    L, d = m["n_layers"], m["d_model"]
    return {"attn_in": (L, d, d), "wo_in": (L, d, d), "mlp_in": (L, d, d),
            "down_in": (L, m["d_ff"], m["d_ff"])}


@partial(jax.jit, static_argnames=("m", "quant"), donate_argnums=(1,))
def _gram_step(params, grams, tokens, *, m, quant):
    m = dict(m)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    out = dict(grams)
    for i in range(m["n_layers"]):
        def record(kind, a, i=i):
            a = _act_q(a.reshape(-1, a.shape[-1]).astype(F32), quant)
            out[kind] = out[kind].at[i].add(
                jnp.matmul(a.T, a, precision=HIGHEST))
        x = layer(params["layers"], i, x, m, quant=quant, record=record)
    return out


def calib_grams(params, batches, m: dict, quant=None) -> dict:
    """Σ over calibration batches of each site input's Gram, per layer.

    ``batches``: iterable of (B, S) token arrays, run one at a time so
    the reference fits beside the program's outputs.
    """
    grams = {k: jnp.zeros(s, F32) for k, s in gram_shapes(m).items()}
    key = tuple(sorted(m.items()))
    for toks in batches:
        grams = _gram_step(params, grams, toks, m=key, quant=quant)
    return grams


def _logits_at(params, masks, tokens, pos, m, quant):
    """Logits (P, vocab) of the masked model at positions ``pos`` of the
    single sequence ``tokens`` (1, S); later tokens never reach them."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    for i in range(m["n_layers"]):
        x = layer(params["layers"], i, x, m, quant=quant,
                  masks=masks["layers"])
    h = rmsnorm(x[0, pos], params["ln_f"]["scale"])
    head = params["embed"] if m["tie_embeddings"] else params["head"]
    return mm(h, head, quant)


def _gap(lg, tok):
    return jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, tok[:, None], axis=-1)[:, 0]


@partial(jax.jit, static_argnames=("m",))
def served_gaps(params, masks, tokens, pos, served, *, m):
    """How far each served token's float32 logit lies below the best."""
    return _gap(_logits_at(params, masks, tokens, pos, dict(m), None),
                served)


@partial(jax.jit, static_argnames=("m", "quant"))
def control_gaps(params, masks, tokens, pos, *, m, quant="fp8"):
    """The same gap for the token the lower precision puts first."""
    m = dict(m)
    low = jnp.argmax(_logits_at(params, masks, tokens, pos, m, quant), -1)
    return _gap(_logits_at(params, masks, tokens, pos, m, None), low)


@jax.jit
def row_loss(W, M, G):
    """Exact per-row loss (w - m⊙w)ᵀ G (w - m⊙w), (R,).

    The product is materialised before the reduction: fused into one
    kernel, the TPU compiler has returned wrong sums for this form.
    """
    x = (1.0 - M.astype(F32)) * W.astype(F32)
    xg = jax.lax.optimization_barrier(jnp.matmul(x, G, precision=HIGHEST))
    return jnp.sum(x * xg, axis=-1)


@partial(jax.jit, static_argnames=("keep",))
def wanda_mask(W, G, *, keep: int):
    """Keep the ``keep`` largest |w_ij|·sqrt(G_jj) of each row."""
    score = jnp.abs(W.astype(F32)) * jnp.sqrt(jnp.maximum(jnp.diagonal(G),
                                                          0.0))[None, :]
    _, idx = jax.lax.top_k(score, keep)
    rows = jnp.arange(W.shape[0])[:, None]
    return jnp.zeros(W.shape, F32).at[rows, idx].set(1.0)


def model_fields(cfg_doc: dict) -> dict:
    """The fields of a configuration file the reference reads."""
    mdl = cfg_doc["model"]
    return {k: mdl[k] for k in ("n_layers", "d_model", "n_heads",
                                "n_kv_heads", "d_ff", "mlp", "act",
                                "rope_pct", "rope_theta", "tie_embeddings",
                                "vocab_size")}
