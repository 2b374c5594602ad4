"""Paged KV cache: fixed-size pages, per-session page tables, byte accounting.

The storage layer of the serving stack. The models' attention code keeps
wanting a *dense* cache — contiguous (B, S, kvH, dh) rows — but sessions
arrive, pause, and finish at their own pace, so tying a session's KV to
a dense batch row for its whole lifetime strands capacity. This module
decouples the two:

* KV lives in a **page pool**: two arrays (k and v) of shape
  ``(L, n_pages + 1, page, kvH, dh)`` — the extra page at index
  ``n_pages`` is scratch (see below) and never allocated.
* A **page table** per session id maps the session's token positions
  ``[0, length)`` onto pages in order; tables are host-side (tiny), the
  pool is device-side (and shards over a mesh via
  ``dist.specs.page_pspecs`` — kv-head dim over "model", exactly like
  the dense cache it mirrors).
* ``load`` gathers a session's pages into a dense slot row for the
  scheduler's working decode cache; ``store`` scatters a slot row back.
  Both are jitted gathers over a *fixed-length* page-id vector (the slot
  capacity ÷ page size), padded with the scratch page id — so join/leave
  of sessions never changes a compiled shape. Scatters aimed at the
  scratch page are discarded by construction; gathers from it are masked
  by the position row (see below).

Positions are NOT stored in pages. The scheduler writes a session's
tokens contiguously (slot index i holds the key for absolute position
i — bucketed-prefill pads at i ≥ length are garbage by contract), so
``load`` reconstructs the position row as ``iota < length ? iota : -1``,
which is precisely the mask ``models.attention`` expects for empty
slots. One invariant instead of a third pool array.

Capacity accounting is in bytes: ``page_bytes`` is the full k+v
footprint of one page across all layers, ``used_bytes`` counts allocated
pages (the scratch page is excluded from both capacity and use). The
scheduler's admission control is one ``can_admit`` call; the leak tests
assert ``used_bytes`` returns to zero when every session is freed.

``defrag`` compacts live pages to the front of the pool (one gather),
rewriting tables — after heavy churn the free list fragments, and a
compacted pool keeps gather indices dense (locality) and makes the
high-water mark readable.
"""
from __future__ import annotations

import dataclasses
import heapq
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common
from repro.runtime import trace


@dataclasses.dataclass
class Session:
    """One session's slice of the pool: ordered pages + token count."""

    pages: list[int]
    length: int = 0                   # real tokens stored (cache positions)
    reserved: int = 0                 # tokens the pages can hold


@dataclasses.dataclass
class HostSpill:
    """A session evicted to host memory, page-granular and exact.

    ``k``/``v`` are the scratch-padded page blocks a ``load`` of the
    session would gather — fixed slot-width numpy arrays, so
    ``restore_spill`` replays the same compiled scatter ``store`` uses
    and the round trip is bitwise. ``length`` is the real token count;
    padding pages beyond ``pages_for(length)`` carry garbage and land on
    the scratch page on restore.
    """

    sid: object
    length: int
    k: np.ndarray
    v: np.ndarray

    @property
    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes


@partial(jax.jit, donate_argnums=(0, 1))
def _scatter_pages(pool_k, pool_v, k_pages, v_pages, pids):
    """Write (L, n_slot_pages, page, kvH, dh) rows into pages ``pids``.

    Duplicate ids (the scratch-page padding) are benign: every duplicate
    targets the scratch page, whose contents are never trusted.
    """
    return (pool_k.at[:, pids].set(k_pages.astype(pool_k.dtype)),
            pool_v.at[:, pids].set(v_pages.astype(pool_v.dtype)))


@jax.jit
def _gather_pages(pool_k, pool_v, pids, length):
    """Pages ``pids`` -> dense (L, C, kvH, dh) rows + (C,) position row."""
    k = common.pages_to_rows(pool_k[:, pids], axis=1)
    v = common.pages_to_rows(pool_v[:, pids], axis=1)
    idx = jnp.arange(k.shape[1], dtype=jnp.int32)
    pos = jnp.where(idx < length, idx, -1)
    return k, v, pos


class PagedKVCache:
    """Fixed-size-page KV store with per-session page tables.

    Args:
        cfg: arch config (layer/head geometry + cache dtype). Only plain
            decoder-only transformers are supported — the paged layout
            mirrors their (L, S, kvH, dh) cache; recurrent families and
            cross-attention caches have no per-token KV pages.
        n_pages: pool capacity in pages (one scratch page is allocated on
            top, excluded from accounting).
        page_size: tokens per page. Slot capacities handed to ``load``
            must divide by it.
        mesh: optional ``jax.sharding.Mesh`` — the pool is placed with
            ``dist.specs.page_pspecs`` (kv heads over "model").
    """

    def __init__(self, cfg, *, n_pages: int, page_size: int, mesh=None):
        if getattr(cfg, "cross_attn_every", 0) or not getattr(
                cfg, "n_kv_heads", 0):
            raise NotImplementedError(
                "paged KV cache supports plain decoder-only transformers")
        if n_pages < 1 or page_size < 1:
            raise ValueError("need n_pages >= 1 and page_size >= 1")
        self.cfg = cfg
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        self.mesh = mesh
        L, kvh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        dt = jnp.dtype(cfg.dtype)
        shape = (L, n_pages + 1, page_size, kvh, dh)
        self.k = jnp.zeros(shape, dt)
        self.v = jnp.zeros(shape, dt)
        if mesh is not None:
            from repro.dist import specs as specs_lib
            sh = specs_lib.named(mesh, specs_lib.page_pspecs(
                cfg, {"k": self.k, "v": self.v}, mesh))
            self.k = jax.device_put(self.k, sh["k"])
            self.v = jax.device_put(self.v, sh["v"])
        self.page_bytes = 2 * L * page_size * kvh * dh * dt.itemsize
        self._free: list[int] = list(range(n_pages))   # min-heap of page ids
        heapq.heapify(self._free)
        self._table: dict = {}
        # inter-pool transfer accounting (see ``ship_pages``): real page
        # bytes that left / entered this pool, scratch padding excluded
        self.shipped_bytes_out = 0
        self.shipped_bytes_in = 0
        # host-spill accounting (see ``spill``/``restore_spill``)
        self.spilled_bytes_out = 0
        self.spilled_bytes_in = 0
        # fault-injection seam: called as hook(pool, need_pages) before
        # any reservation that would actually take pages; an injected
        # MemoryError here is indistinguishable from real exhaustion to
        # callers, which is the point (serve.faultinject)
        self.fault_hook = None

    # -- accounting ---------------------------------------------------------

    @property
    def scratch_page(self) -> int:
        return self.n_pages

    @property
    def capacity_bytes(self) -> int:
        return self.n_pages * self.page_bytes

    @property
    def used_bytes(self) -> int:
        return (self.n_pages - len(self._free)) * self.page_bytes

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.page_size)

    def can_admit(self, n_tokens: int) -> bool:
        """Would ``alloc(sid, n_tokens)`` succeed right now?"""
        return self.pages_for(n_tokens) <= len(self._free)

    def can_extend(self, sid, n_tokens: int) -> bool:
        """Would ``extend(sid, n_tokens)`` succeed right now?"""
        need = self.pages_for(n_tokens) - len(self._table[sid].pages)
        return need <= len(self._free)

    def sessions(self) -> list:
        return list(self._table)

    def length(self, sid) -> int:
        return self._table[sid].length

    def page_table(self, sid) -> tuple:
        return tuple(self._table[sid].pages)

    # -- alloc / free -------------------------------------------------------

    def alloc(self, sid, n_tokens: int) -> None:
        """Reserve pages for ``n_tokens`` under a new session id."""
        if sid in self._table:
            raise ValueError(f"session {sid!r} already allocated")
        sess = Session(pages=[])
        self._table[sid] = sess
        try:
            self._reserve(sess, n_tokens)
        except MemoryError:
            del self._table[sid]
            raise

    def extend(self, sid, n_tokens: int) -> None:
        """Grow a session's reservation to cover ``n_tokens`` total."""
        self._reserve(self._table[sid], n_tokens)

    def _reserve(self, sess: Session, n_tokens: int) -> None:
        need = self.pages_for(n_tokens) - len(sess.pages)
        if need > 0 and self.fault_hook is not None:
            self.fault_hook(self, need)
        if need > len(self._free):
            raise MemoryError(
                f"paged KV cache exhausted: need {need} pages, "
                f"{len(self._free)} free of {self.n_pages}")
        for _ in range(max(need, 0)):
            sess.pages.append(heapq.heappop(self._free))
        sess.reserved = len(sess.pages) * self.page_size

    def free(self, sid) -> None:
        """Release a session's pages back to the pool."""
        sess = self._table.pop(sid)
        for p in sess.pages:
            heapq.heappush(self._free, p)

    # -- page <-> slot-row copies ------------------------------------------

    def _padded_pids(self, sess: Session, n_tokens: int,
                     capacity: int) -> jnp.ndarray:
        """Page ids covering ``n_tokens``, scratch-padded to the slot width.

        Only the prefix of the session's table that real tokens occupy is
        addressed — a session may hold MORE pages than one slot-row copy
        touches (reserved up front for its full prompt+output budget,
        stored from a shorter prefill row) as long as the live prefix
        fits.
        """
        if capacity % self.page_size:
            raise ValueError(f"slot capacity {capacity} not divisible by "
                             f"page size {self.page_size}")
        n_used = self.pages_for(n_tokens)
        n_slot = capacity // self.page_size
        if n_used > n_slot:
            raise ValueError(f"{n_tokens} tokens need {n_used} pages, slot "
                             f"fits {n_slot}")
        pad = [self.scratch_page] * (n_slot - n_used)
        return jnp.asarray(sess.pages[:n_used] + pad, jnp.int32)

    def store(self, sid, k_row: jnp.ndarray, v_row: jnp.ndarray,
              length: int) -> None:
        """Scatter a dense slot row (L, C, kvH, dh) into ``sid``'s pages.

        ``length`` is the number of real tokens in the row (slot indices
        ≥ length are garbage by the contiguity contract); the reservation
        grows to cover it if needed.
        """
        sess = self._table[sid]
        if length > sess.reserved:
            self._reserve(sess, length)
        pids = self._padded_pids(sess, length, k_row.shape[1])
        kp = common.rows_to_pages(k_row, self.page_size, axis=1)
        vp = common.rows_to_pages(v_row, self.page_size, axis=1)
        kp, vp = self._place(kp, vp)
        self.k, self.v = _scatter_pages(self.k, self.v, kp, vp, pids)
        sess.length = int(length)

    def _place(self, kp, vp):
        """Put a page block onto this pool's mesh slice before a scatter.

        A pool on its own mesh slice (disaggregated serving) receives
        rows computed on a DIFFERENT device set; jit refuses inputs
        committed to two device sets, so the block is explicitly
        transferred first. With no mesh this is a no-op — single-pool
        callers keep their zero-copy path.
        """
        if self.mesh is None:
            return kp, vp
        from repro.dist import specs as specs_lib
        sh = specs_lib.named(self.mesh, specs_lib.page_pspecs(
            self.cfg, {"k": kp, "v": vp}, self.mesh))
        return jax.device_put(kp, sh["k"]), jax.device_put(vp, sh["v"])

    def load(self, sid, capacity: int):
        """Gather ``sid``'s pages into dense rows of ``capacity`` tokens.

        Returns ``(k (L, C, kvH, dh), v, pos (C,) int32, length)`` —
        ``pos`` is ``[0..length)`` then ``-1``, the exact empty-slot mask
        the attention cache expects.
        """
        sess = self._table[sid]
        pids = self._padded_pids(sess, sess.length, capacity)
        k, v, pos = _gather_pages(self.k, self.v, pids,
                                  jnp.int32(sess.length))
        return k, v, pos, sess.length

    # -- host spill (eviction under page pressure) --------------------------

    def spill(self, sid, *, capacity: int) -> HostSpill:
        """Evict ``sid`` to host memory and free its pages.

        The gather is the same fixed-shape scratch-padded page indexing
        ``load`` uses, pulled to host as numpy — so spill→restore→load
        round-trips bitwise, and one program per slot width serves every
        session regardless of page count. The session disappears from
        the pool (its pages return to the free list) until
        ``restore_spill`` re-admits it.
        """
        sess = self._table[sid]
        pids = self._padded_pids(sess, sess.length, capacity)
        k, v = trace.wait((self.k[:, pids], self.v[:, pids]), "kv.spill",
                          jax.device_get)
        out = HostSpill(sid=sid, length=sess.length, k=k, v=v)
        self.spilled_bytes_out += self.pages_for(sess.length) * self.page_bytes
        self.free(sid)
        return out

    def restore_spill(self, spill: HostSpill, *, sid=None) -> None:
        """Re-admit a spilled session; raises MemoryError before mutation.

        Allocates exactly ``pages_for(spill.length)`` pages (callers
        growing the session for further decode extend it afterwards) and
        scatters the host block back through the scratch-padded path —
        the padding pages land on the scratch page and are discarded.
        """
        sid = spill.sid if sid is None else sid
        self.alloc(sid, spill.length)        # raises before any mutation
        sess = self._table[sid]
        pids = jnp.asarray(
            sess.pages + [self.scratch_page] * (spill.k.shape[1]
                                                - len(sess.pages)),
            jnp.int32)
        kp, vp = self._place(jnp.asarray(spill.k), jnp.asarray(spill.v))
        self.k, self.v = _scatter_pages(self.k, self.v, kp, vp, pids)
        sess.length = int(spill.length)
        self.spilled_bytes_in += self.pages_for(spill.length) * self.page_bytes

    # -- defrag -------------------------------------------------------------

    def defrag(self) -> int:
        """Compact live pages to the front of the pool; returns #moved.

        Rebuilds every page table so sessions see their pages at dense
        low ids (in session order), and the free list becomes the
        contiguous tail — one whole-pool gather, tables rewritten in
        place. A no-op (0 moved) when already compact.
        """
        live: list[int] = [p for s in self._table.values() for p in s.pages]
        if live == list(range(len(live))):
            return 0
        leftover = sorted(set(range(self.n_pages)) - set(live))
        perm = jnp.asarray(live + leftover + [self.scratch_page], jnp.int32)
        self.k = jax.jit(lambda a, i: a[:, i], donate_argnums=0)(self.k, perm)
        self.v = jax.jit(lambda a, i: a[:, i], donate_argnums=0)(self.v, perm)
        remap = {old: new for new, old in enumerate(live)}
        moved = sum(1 for old, new in remap.items() if old != new)
        for s in self._table.values():
            s.pages = [remap[p] for p in s.pages]
        self._free = list(range(len(live), self.n_pages))
        heapq.heapify(self._free)
        return moved


# ---------------------------------------------------------------------------
# inter-pool transport (disaggregated serving)
# ---------------------------------------------------------------------------

def ship_pages(src: PagedKVCache, dst: PagedKVCache, sid, *,
               capacity: int, dst_sid=None) -> int:
    """Move a session's KV pages from one pool to another; returns bytes.

    The transport unit of prefill/decode disaggregation: a session
    prefilled into the prefill pool (one mesh slice) ships to the decode
    pool (another slice) before it may join the decode batch. The
    transfer is FIXED-SHAPE and page-granular — the source pages gather
    scratch-padded to ``capacity // page_size`` page slots (exactly the
    ``load`` discipline), the block is ``device_put`` onto the
    destination pool's placement, and a scratch-padded scatter installs
    it — so shipping compiles ONE program per slot width regardless of
    how many pages a session actually holds. Scatters aimed at either
    scratch page are discarded by construction.

    Only *real* pages count in the byte ledger: ``src.shipped_bytes_out``
    and ``dst.shipped_bytes_in`` both grow by ``pages · page_bytes``.
    The destination session (``dst_sid``, default the same id) is
    allocated here for exactly the session's stored length — callers
    growing it (prompt + output budget) extend it afterwards; on an
    exhausted destination pool the MemoryError propagates BEFORE any
    state changes, so the source session stays intact and shippable
    later. The source pages are freed once the scatter lands.
    """
    if src.page_size != dst.page_size:
        raise ValueError(f"page-size mismatch: src {src.page_size}, "
                         f"dst {dst.page_size}")
    sess = src._table[sid]
    dst_sid = sid if dst_sid is None else dst_sid
    n_tokens = sess.length
    dst.alloc(dst_sid, n_tokens)             # raises before any mutation
    n_used = src.pages_for(n_tokens)
    src_pids = src._padded_pids(sess, n_tokens, capacity)
    kp, vp = src.k[:, src_pids], src.v[:, src_pids]
    kp, vp = dst._place(kp, vp)
    d = dst._table[dst_sid]
    dst_pids = jnp.asarray(
        d.pages + [dst.scratch_page] * (len(src_pids) - len(d.pages)),
        jnp.int32)
    dst.k, dst.v = _scatter_pages(dst.k, dst.v, kp, vp, dst_pids)
    d.length = n_tokens
    src.free(sid)
    moved = n_used * src.page_bytes
    src.shipped_bytes_out += moved
    dst.shipped_bytes_in += moved
    return moved
