"""Packed sparse weight export: mask -> servable formats.

A refined mask is only worth anything if the serving path stops paying
for the zeros. This module converts ``(W, mask)`` pairs into the two
formats the serving runtime executes (``repro.kernels.spmm``):

* ``nm24`` — N:M semi-structured (the flagship 2:4): per m-block of each
  row, the n kept values are stored contiguously plus a uint8
  *within-block* column index — the same metadata layout sparse tensor
  cores consume (Mishra et al. 2021; MaskLLM). Bytes at rest:
  ``n/m`` of the values + 1 byte/kept-weight of metadata.
* ``gathered`` — per-row kept-column indices for *equal-R* unstructured
  rows. SparseSwaps preserves the warmstart's exact per-row keep count
  by construction (1-swaps are count-preserving), so every `PerRow`
  mask it emits is representable; rows with unequal support are
  rejected loudly.

``pack``/``unpack`` round-trip bit-exactly: ``unpack(pack(w, m)) ==
w * m`` for every dtype the models serve (f32/bf16).

``PackedWeight`` is a registered pytree whose data leaves carry any
leading stack dims (layers, experts), so packed params slot into the
models' ``lax.scan`` over stacked layers and into ``dist.specs``
sharding unchanged. Entry points from pruning artifacts:
``from_report`` (an in-memory ``PruneReport``) and ``from_executor_ckpt``
(a ``PruneExecutor`` checkpoint directory — also what fixes
``launch/serve.py --masks-from``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import masks as masks_lib

FORMATS = ("nm24", "gathered")


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("values", "idx"),
                   meta_fields=("fmt", "d_in", "n", "m"))
@dataclasses.dataclass
class PackedWeight:
    """One packed prunable linear, leading stack dims preserved.

    ``values``: (..., d_out, k) kept weights in ascending-column order;
    ``idx``: (..., d_out, k) column metadata — uint8 within-block
    positions for ``nm24``, int32 absolute columns for ``gathered``.
    Registered as a pytree (values/idx are data, the format fields are
    static), so a stacked PackedWeight scans, shards and jits like any
    weight leaf.
    """

    values: jnp.ndarray
    idx: jnp.ndarray
    fmt: str            # "nm24" | "gathered"
    d_in: int           # original input dim (the packed-away axis)
    n: int = 0          # kept per block (nm24 only)
    m: int = 0          # block size (nm24 only)

    @property
    def shape(self) -> tuple[int, ...]:
        """The dense (..., d_out, d_in) shape this leaf stands in for."""
        return (*self.values.shape[:-1], self.d_in)

    @property
    def k(self) -> int:
        """Kept weights per row."""
        return int(self.values.shape[-1])

    @property
    def nbytes(self) -> int:
        """Resident bytes of the packed representation."""
        return int(self.values.nbytes + self.idx.nbytes)

    @property
    def dense_nbytes(self) -> int:
        """Bytes the dense (masked) weight would occupy at this dtype."""
        return int(self.values.dtype.itemsize * np.prod(self.shape))


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def _check_mask01(mask: np.ndarray) -> np.ndarray:
    m = np.asarray(mask)
    if not np.all((m == 0) | (m == 1)):
        raise ValueError("mask must be exactly 0/1")
    return m.astype(np.float32)


def pack_nm(w: jnp.ndarray, mask: jnp.ndarray, *, n: int = 2,
            m: int = 4) -> PackedWeight:
    """Pack an N:M mask: (..., d_out, d_in) -> values + uint8 block idx.

    Every m-block of every row must keep exactly n entries; anything
    else is a corrupt mask for this format and raises.
    """
    w = jnp.asarray(w)
    d_in = int(w.shape[-1])
    if d_in % m:
        raise ValueError(f"d_in={d_in} not divisible by M={m}")
    mk = _check_mask01(mask)
    nb = d_in // m
    mb = mk.reshape(*mk.shape[:-1], nb, m)
    per_block = mb.sum(axis=-1)
    if not np.all(per_block == n):
        bad = int((per_block != n).sum())
        raise ValueError(
            f"mask is not {n}:{m}: {bad} block(s) keep != {n} entries")
    # kept entries in ascending column order: stable argsort of (1 - m)
    order = np.argsort(1.0 - mb, axis=-1, kind="stable")[..., :n]
    idx = jnp.asarray(order.astype(np.uint8))           # within-block pos
    cols = (order + m * np.arange(nb)[:, None]).reshape(*w.shape[:-1],
                                                       nb * n)
    return PackedWeight(values=take_cols(w, cols),
                        idx=idx.reshape(*w.shape[:-1], nb * n),
                        fmt="nm24", d_in=d_in, n=n, m=m)


def take_cols(w: jnp.ndarray, cols: np.ndarray) -> jnp.ndarray:
    """w[..., r, cols[..., r, :]] — the packers' one device gather.

    ``cols`` holds absolute column indices, so the gather runs on ``w``'s
    own (..., d_out, d_in) layout: a (..., d_in/m, m) view would put m
    on the TPU's 128-wide lane axis and pad it 32x (13.7 GB for a
    stacked 4-layer d_ff x d_model group at m = 4).
    """
    return jnp.take_along_axis(w, jnp.asarray(cols.astype(np.int32)),
                               axis=-1)


def pack_gathered(w: jnp.ndarray, mask: jnp.ndarray) -> PackedWeight:
    """Pack an equal-support unstructured mask: per-row column gather.

    Every row must keep the same number of entries R (SparseSwaps'
    ``PerRow`` masks guarantee this); rows with unequal support raise.
    """
    w = jnp.asarray(w)
    d_in = int(w.shape[-1])
    mk = _check_mask01(mask)
    per_row = mk.sum(axis=-1)
    k = int(per_row.reshape(-1)[0])
    if not np.all(per_row == k):
        lo, hi = int(per_row.min()), int(per_row.max())
        raise ValueError(
            f"gathered format needs equal per-row support; got rows "
            f"keeping between {lo} and {hi} entries")
    if k == 0:
        raise ValueError("gathered format cannot represent all-pruned rows")
    order = np.argsort(1.0 - mk, axis=-1, kind="stable")[..., :k]
    order = np.ascontiguousarray(np.sort(order, axis=-1))  # ascending cols
    return PackedWeight(values=take_cols(w, order),
                        idx=jnp.asarray(order.astype(np.int32)),
                        fmt="gathered", d_in=d_in)


def pack(w: jnp.ndarray, mask: jnp.ndarray, fmt: str, *, n: int = 2,
         m: int = 4) -> PackedWeight:
    """Dispatching packer; ``fmt`` in {"nm24", "gathered"}."""
    if fmt == "nm24":
        return pack_nm(w, mask, n=n, m=m)
    if fmt == "gathered":
        return pack_gathered(w, mask)
    raise ValueError(f"unknown packed format {fmt!r} (want one of {FORMATS})")


def unpack(pw: PackedWeight) -> jnp.ndarray:
    """Exact inverse: the dense ``w * mask`` this PackedWeight encodes."""
    lead = pw.values.shape[:-1]
    if pw.fmt == "nm24":
        nb = pw.d_in // pw.m
        vals = pw.values.reshape(*lead, nb, pw.n)
        idx = pw.idx.reshape(*lead, nb, pw.n).astype(jnp.int32)
        # disjoint within-block positions -> one-hot scatter is exact
        oh = jax.nn.one_hot(idx, pw.m, dtype=pw.values.dtype)
        dense = jnp.einsum("...s,...sj->...j", vals, oh)
        return dense.reshape(*lead, pw.d_in)
    oh = jax.nn.one_hot(pw.idx, pw.d_in, dtype=pw.values.dtype)
    return jnp.einsum("...s,...sj->...j", pw.values, oh)


def mask_of(pw: PackedWeight) -> jnp.ndarray:
    """The 0/1 keep-mask this PackedWeight encodes (f32)."""
    return unpack(dataclasses.replace(
        pw, values=jnp.ones_like(pw.values, dtype=jnp.float32),
        idx=pw.idx))


# ---------------------------------------------------------------------------
# whole-model packing
# ---------------------------------------------------------------------------

def _site_paths(cfg) -> list[tuple[str, tuple[str, ...]]]:
    """(site name, param path) for every prunable site of ``cfg``.

    Site names mirror param paths 1:1 in the family tables
    (``pruning.sites``), so the path is the dotted name split.
    """
    from repro.pruning import sites as sites_lib
    return [(name, ppath) for name, ppath, _, _ in sites_lib._table(cfg)]


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _maybe_get(tree, path):
    for k in path:
        if not isinstance(tree, dict) or k not in tree:
            return None
        tree = tree[k]
    return tree


def _set(tree, path, leaf):
    node = tree
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = leaf


def pack_tree(cfg, params: dict, masks: dict, fmt: str = "nm24", *,
              n: int = 2, m: int = 4) -> dict:
    """Replace every masked prunable leaf of ``params`` with PackedWeight.

    Sites without a mask entry (skip-rules) stay dense; ``fmt`` applies
    uniformly — a mask a format cannot represent raises with the site
    name, it is never silently served dense. For ``nm24``, the block
    shape (n, m) is inferred per site from the mask when it isn't 2:4.
    """
    out = jax.tree.map(lambda x: x, params)     # shallow-ish copy of dicts
    for name, ppath in _site_paths(cfg):
        mask = _maybe_get(masks, ppath)
        if mask is None:
            continue
        w = _get(params, ppath)
        try:
            if fmt == "nm24":
                ni, mi = infer_nm(mask, default=(n, m))
                pw = pack_nm(w, mask, n=ni, m=mi)
            else:
                pw = pack(w, mask, fmt)
        except ValueError as e:
            raise ValueError(f"site {name!r}: {e}") from None
        _set(out, ppath, pw)
    return out


def infer_nm(mask: jnp.ndarray, *, default=(2, 4),
             candidates=((2, 4), (4, 8), (1, 4), (2, 8), (1, 2),
                         (4, 16), (8, 16))) -> tuple[int, int]:
    """Smallest (n, m) block shape an N:M mask satisfies.

    Tries the default first (the hardware-native 2:4), then the usual
    suspects; raises when none fits — the caller reports the site.
    """
    mk = np.asarray(mask)
    d_in = mk.shape[-1]
    for ni, mi in (default, *candidates):
        if d_in % mi:
            continue
        blocks = mk.reshape(*mk.shape[:-1], d_in // mi, mi).sum(axis=-1)
        if np.all(blocks == ni):
            return ni, mi
    raise ValueError("mask is not N:M for any supported block shape")


def representable(cfg, masks: dict, fmt: str) -> bool:
    """Whether every masked site of ``cfg`` can be packed as ``fmt``.

    A mask property only — no weights are touched, so callers can probe
    formats (bench format selection) without paying a pack.
    """
    if fmt not in FORMATS:
        raise ValueError(f"unknown packed format {fmt!r}")
    for _, ppath in _site_paths(cfg):
        mask = _maybe_get(masks, ppath)
        if mask is None:
            continue
        mk = np.asarray(mask)
        if fmt == "nm24":
            try:
                infer_nm(mk)
            except ValueError:
                return False
        else:
            per_row = mk.sum(axis=-1)
            if per_row.min() != per_row.max() or per_row.max() == 0:
                return False
    return True


def packed_bytes(params: dict) -> int:
    """Resident weight bytes of a (possibly packed) param tree."""
    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(leaf, PackedWeight):
            total += leaf.nbytes
        else:
            total += int(leaf.nbytes)
    return total


def from_report(cfg, params: dict, report, fmt: str = "nm24") -> dict:
    """Pack from an in-memory ``PruneReport`` (or a bare masks tree)."""
    masks = getattr(report, "masks", report)
    return pack_tree(cfg, params, masks, fmt)


def from_executor_ckpt(cfg, params: dict, ckpt_dir: str | Path,
                       fmt: str = "nm24") -> dict:
    """Pack from a ``PruneExecutor``/launcher checkpoint directory.

    SparseGPT group checkpoints pack their *updated* weights.
    """
    masks, params = load_masks_and_weights(cfg, params, ckpt_dir)
    return pack_tree(cfg, params, masks, fmt)


def load_packed_tree(params: dict, out_dir: str | Path) -> dict:
    """Inverse of ``PruneExecutor.export_packed``: a pre-packed param tree.

    Restores the values/idx checkpoint under ``<out_dir>/packed`` and
    splices ``PackedWeight`` leaves into a copy of ``params`` at the
    recorded site paths — serving needs no re-pack and never touches the
    masks.
    """
    from repro import ckpt

    d = Path(out_dir) / "packed"
    step = ckpt.latest_valid(d)
    if step is None:
        raise FileNotFoundError(f"no valid packed checkpoint under {d}")
    man = json.loads((d / f"step_{step:08d}" / "MANIFEST.json").read_text())
    meta = man["extra"]["sites"]
    flat_target = {e["path"]: jax.ShapeDtypeStruct(tuple(e["shape"]),
                                                   e["dtype"])
                   for e in man["leaves"]}
    restored, _ = ckpt.restore(d, step, flat_target)
    out = jax.tree.map(lambda x: x, params)
    for name, mt in meta.items():
        pw = PackedWeight(
            values=jnp.asarray(restored[f"values/{name}"]),
            idx=jnp.asarray(restored[f"idx/{name}"]),
            fmt=mt["fmt"], d_in=int(mt["d_in"]), n=int(mt["n"]),
            m=int(mt["m"]))
        _set(out, tuple(name.split(".")), pw)
    return out


# ---------------------------------------------------------------------------
# mask-checkpoint loading (the --masks-from path)
# ---------------------------------------------------------------------------

def load_mask_tree(cfg, params: dict, ckpt_dir: str | Path) -> dict:
    """Assemble a masks pytree from any pruning-run artifact directory.

    Accepts, in resolution order:

    * an executor checkpoint dir (``<dir>/groups/<site>/step_*``) — the
      per-group masks the ``PruneExecutor`` publishes as it runs; sites
      without a (valid) group checkpoint are served dense;
    * a masks-tree checkpoint (``<dir>/step_*`` written by
      ``ckpt.save(dir, step, report.masks)``);
    * a launcher ``--out-dir`` root — resolves ``<dir>/masks`` then
      ``<dir>/prune_ckpt`` by the two rules above.
    """
    return load_masks_and_weights(cfg, params, ckpt_dir)[0]


def load_masks_and_weights(cfg, params: dict,
                           ckpt_dir: str | Path) -> tuple[dict, dict]:
    """``load_mask_tree`` plus the weights the masks belong to.

    SparseGPT group checkpoints carry ``new_weights`` (the refiner
    *updates* the surviving weights); serving its masks over the
    original weights would be silently wrong, so the executor-checkpoint
    path splices every saved weight stack into a copy of ``params``.
    Mask-only checkpoints return ``params`` unchanged.
    """
    from repro import ckpt

    d = Path(ckpt_dir)
    if (d / "groups").is_dir():
        return _masks_from_groups(cfg, params, d / "groups")
    if ckpt.steps(d):
        return _masks_from_tree_ckpt(cfg, d), params
    # executor checkpoints first: a launcher --out-dir root holds BOTH a
    # mask-only tree (masks/) and the group ckpts (prune_ckpt/), and only
    # the latter carry sparsegpt's updated weights
    for sub in ("prune_ckpt", "masks"):
        if (d / sub).exists():
            try:
                masks, params = load_masks_and_weights(cfg, params, d / sub)
            except FileNotFoundError:
                continue
            if (d / "weights").is_dir():   # export_packed's sparsegpt dump
                params = _splice_weights(params, d / "weights")
            return masks, params
    raise FileNotFoundError(
        f"no mask checkpoint under {d} (want groups/<site>/step_* or "
        "step_* or masks/|prune_ckpt/)")


def _splice_weights(params: dict, d: Path) -> dict:
    """Overlay an exported updated-weight checkpoint onto ``params``.

    ``d`` holds a flat {dotted site name: (stack..., d_out, d_in)} tree
    (``PruneExecutor.export_packed`` writes it for sparsegpt runs).
    """
    from repro import ckpt

    step = ckpt.latest_valid(d)
    if step is None:
        return params
    man = json.loads((d / f"step_{step:08d}" / "MANIFEST.json").read_text())
    target = {e["path"]: jax.ShapeDtypeStruct(tuple(e["shape"]), e["dtype"])
              for e in man["leaves"]}
    restored, _ = ckpt.restore(d, step, target)
    out = jax.tree.map(lambda x: x, params)
    for name, leaf in restored.items():
        ppath = tuple(name.split("."))
        old = _get(params, ppath)
        _set(out, ppath, jnp.asarray(leaf).astype(old.dtype))
    return out


def _masks_from_groups(cfg, params: dict,
                       groups_dir: Path) -> tuple[dict, dict]:
    from repro import ckpt
    from repro.pruning import sites as sites_lib

    specs = {s.name: s for s in sites_lib.site_specs(cfg, params)}
    tree: dict = {}
    new_params = params
    found = 0
    for name, ppath in _site_paths(cfg):
        gdir = groups_dir / name
        step = ckpt.latest_valid(gdir) if gdir.is_dir() else None
        if step is None:
            continue
        spec = specs[name]
        shape = (spec.n_instances, spec.d_out, spec.d_in)
        man = json.loads((gdir / f"step_{step:08d}" / "MANIFEST.json")
                         .read_text())
        saved = {e["path"]: e["dtype"] for e in man["leaves"]}
        target = {"masks": jax.ShapeDtypeStruct(shape, jnp.float32)}
        if "new_weights" in saved:           # sparsegpt: updated weights
            target["new_weights"] = jax.ShapeDtypeStruct(
                shape, saved["new_weights"])
        restored, _ = ckpt.restore(gdir, step, target)

        def unstack(a):
            a = jnp.asarray(a)
            return (a.reshape(*spec.stack_shape, spec.d_out, spec.d_in)
                    if spec.stack_shape else a[0])

        node = tree
        for k in ppath[:-1]:
            node = node.setdefault(k, {})
        node[ppath[-1]] = unstack(restored["masks"])
        if "new_weights" in restored:
            if new_params is params:
                new_params = jax.tree.map(lambda x: x, params)
            old = _get(params, ppath)
            _set(new_params, ppath,
                 unstack(restored["new_weights"]).astype(old.dtype))
        found += 1
    if not found:
        raise FileNotFoundError(
            f"no valid group mask checkpoints under {groups_dir}")
    # keep top-level family keys the models index unconditionally
    for name, _ in _site_paths(cfg):
        tree.setdefault(name.split(".", 1)[0], {})
    return tree, new_params


def _masks_from_tree_ckpt(cfg, d: Path) -> dict:
    """Restore a full masks-tree checkpoint from its own manifest.

    The manifest records every leaf's path/shape/dtype, so the nested
    dict is rebuilt from the flat paths alone; ``cfg`` only backfills
    the top-level family keys the models index unconditionally (an
    all-skip family checkpoints zero leaves).
    """
    from repro import ckpt

    step = ckpt.latest_valid(d)
    if step is None:
        raise FileNotFoundError(f"no valid checkpoint under {d}")
    man = json.loads((d / f"step_{step:08d}" / "MANIFEST.json").read_text())
    flat_target = {e["path"]: jax.ShapeDtypeStruct(tuple(e["shape"]),
                                                   e["dtype"])
                   for e in man["leaves"]}
    restored, _ = ckpt.restore(d, step, flat_target)
    tree: dict = {}
    for path, leaf in restored.items():
        keys = path.split("/")
        node = tree
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = jnp.asarray(leaf)
    for name, _ in _site_paths(cfg):
        tree.setdefault(name.split(".", 1)[0], {})
    return tree
