"""KVCacheStore — the KV storage subsystem behind every serving cache.

Two backends hide behind one interface:

  dense — the seed layout: per-request ``(B, max_context, Hkv, Dh)`` K/V
      buffers, written with ``dynamic_update_slice``. Byte-identical to the
      pre-store code paths (token-equality is tested, not assumed).

  paged — a physical page pool ``(num_pages, page_size, Hkv, Dh)`` shared by
      every request plus a per-row page table ``(B, max_pages)`` mapping
      logical page -> physical page (-1 = unmapped). Admission allocates a
      request's pages from a host-side free list (`PageAllocator`), commits
      scatter accepted tokens into the row's own pages (donated, in place),
      and completion returns the pages to the pool — so batch KV memory
      scales with live tokens, not ``batch * max_context``.

The page size is aligned with the NSA selection-block granularity
(``page_size % sel_block == 0``, default ``page_size == sel_block``): a
selected block index resolves to a page-table entry, turning the paper's
sparse selected-KV gather into natively paged access: one KV head's block is
fetched as one ``(sel_block, Dh)`` slab of its page with a single gather
index. Out-of-range or unmapped lookups read an explicit zero page (never a
silently clamped neighbor) and writes to them are dropped — the
adversarial-index contract ``tests/test_kvstore.py`` pins down.

Device-side state is a plain pytree (`KVView` wraps the per-layer K/V
storage plus the shared page table); host-side page accounting is the
`PageAllocator`. The scheduler gates admission on `PageAllocator.free_count`
so a full pool leaves the queue pending instead of corrupting live rows.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------------ config
@dataclasses.dataclass(frozen=True)
class KVStoreConfig:
    """Hashable store descriptor — part of every jit-cache key that traces
    differently per backend."""

    backend: str = "dense"        # "dense" | "paged"
    page_size: int = 0            # tokens per page (0 -> model's nsa.sel_block)
    num_pages: int = 0            # physical pool pages (0 -> slots * max_pages)

    def __post_init__(self):
        if self.backend not in ("dense", "paged"):
            raise ValueError(f"unknown kv backend {self.backend!r}; "
                             "choose dense or paged")

    @property
    def is_paged(self) -> bool:
        return self.backend == "paged"

    def resolved_page_size(self, model_cfg) -> int:
        ps = self.page_size or (model_cfg.nsa.sel_block
                                if model_cfg.attention == "nsa" else 64)
        if model_cfg.attention == "nsa" and ps % model_cfg.nsa.sel_block:
            raise ValueError(
                f"page_size={ps} must be a multiple of nsa.sel_block="
                f"{model_cfg.nsa.sel_block}: selected-block gather resolves "
                "through the page table, so pages must tile selection blocks")
        return ps

    def logical_pages(self, max_len: int, page_size: int) -> int:
        if max_len % page_size:
            raise ValueError(f"max_context={max_len} must be a multiple of "
                             f"page_size={page_size}")
        return max_len // page_size

    def resolved_num_pages(self, num_slots: int, max_pages_row: int) -> int:
        return self.num_pages or num_slots * max_pages_row


DENSE = KVStoreConfig()


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pages covering ``n_tokens`` committed tokens (at least one page so an
    admitted row always owns a write target)."""
    return max(1, -(-int(n_tokens) // int(page_size)))


# ------------------------------------------------------------------ view
@dataclasses.dataclass
class KVView:
    """Per-layer K/V storage handle.

    dense: k/v are ``(B, S, Hkv, Dh)``, ``pages is None``.
    paged: k/v are the pool ``(P, page_size, Hkv, Dh)`` and ``pages`` is the
    shared ``(B, max_pages)`` int32 page table.
    """

    k: Any
    v: Any
    pages: Any = None

    # ---- static geometry (shapes only — safe under tracing)
    @property
    def is_paged(self) -> bool:
        return self.pages is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        if self.is_paged:
            return self.pages.shape[1] * self.page_size
        return self.k.shape[1]

    @property
    def batch(self) -> int:
        return self.pages.shape[0] if self.is_paged else self.k.shape[0]

    # ---- paged address resolution
    def _phys_page(self, lp):
        """lp (B, ...) logical page indices -> (physical page, valid). Invalid
        (negative / past the table / unmapped) pages resolve to page 0 with
        ``valid`` False: callers read an explicit zero or drop the write."""
        B, MP = self.pages.shape
        phys = jnp.take_along_axis(self.pages,
                                   jnp.clip(lp, 0, MP - 1).reshape(B, -1),
                                   axis=1).reshape(lp.shape)
        ok = (lp >= 0) & (lp < MP) & (phys >= 0)
        return jnp.where(ok, phys, 0), ok

    def _phys_flat(self, tok):
        """tok (B, ...) absolute positions -> flat pool-token index, -1 for
        out-of-range / unmapped (explicit zero page downstream)."""
        ps = self.page_size
        phys, ok = self._phys_page(tok // ps)
        return jnp.where(ok, phys * ps + tok % ps, -1)

    # ---- reads
    def gather_tokens(self, tok):
        """tok (B, *rest) absolute positions -> (k, v) of shape
        (B, *rest, Hkv, Dh); invalid positions read exact zeros."""
        if self.is_paged:
            flat = self._phys_flat(tok)
            P, ps = self.k.shape[0], self.page_size
            kf = self.k.reshape(P * ps, *self.k.shape[2:])
            vf = self.v.reshape(P * ps, *self.v.shape[2:])
            ok = (flat >= 0)[..., None, None]
            idx = jnp.clip(flat, 0, P * ps - 1)
            return jnp.where(ok, kf[idx], 0), jnp.where(ok, vf[idx], 0)
        S = self.k.shape[1]
        ok = ((tok >= 0) & (tok < S))[..., None, None]
        idx = jnp.clip(tok, 0, S - 1)
        B = self.k.shape[0]
        bidx = jnp.arange(B).reshape((B,) + (1,) * (tok.ndim - 1))
        return (jnp.where(ok, self.k[bidx, idx], 0),
                jnp.where(ok, self.v[bidx, idx], 0))

    def gather_blocks(self, idx, sel_block: int):
        """Selected-block gather (head-aligned): idx (B, T, Hkv, n) block
        indices -> k/v (B, T, Hkv, n, sel_block, Dh).

        Paged: a block index is a page-table lookup (pages tile sel blocks),
        resolved once per (row, node, head, block), which then fetches one
        ``(sel_block, Dh)`` slab of its page — not ``sel_block`` rows of one
        token each. Invalid / out-of-range / unmapped blocks read an explicit
        zero page — never a clamped neighbor (see tests/test_kvstore.py
        adversarial sel).
        """
        B, T, Hkv, n = idx.shape
        if self.is_paged:
            m = self.page_size // sel_block                  # blocks per page
            phys, ok = self._phys_page(idx // m)
            sub = idx % m
            hidx = jnp.arange(Hkv).reshape(1, 1, Hkv, 1)
            ok = ok[..., None, None]

            def slabs(pool):       # (P, m, sel_block, Hkv, Dh) -> one slab each
                blocks = pool.reshape((pool.shape[0], m, sel_block)
                                      + pool.shape[2:])
                return jnp.where(ok, blocks[phys, sub, :, hidx], 0)
            return slabs(self.k), slabs(self.v)
        tok = idx[..., None] * sel_block + jnp.arange(sel_block)  # (B,T,Hkv,n,l')
        S = self.k.shape[1]
        ok = ((tok >= 0) & (tok < S))[..., None]
        tokc = jnp.clip(tok, 0, S - 1)
        bidx = jnp.arange(B).reshape(B, 1, 1, 1, 1)
        hidx = jnp.arange(Hkv).reshape(1, 1, Hkv, 1, 1)
        return (jnp.where(ok, self.k[bidx, tokc, hidx], 0),
                jnp.where(ok, self.v[bidx, tokc, hidx], 0))

    def window(self, win_start, W: int):
        """Trailing window [win_start, win_start + W) -> k/v (B, W, Hkv, Dh).
        Dense reproduces the seed's dynamic slice exactly; paged gathers the
        covering logical pages and slices the offset."""
        if not self.is_paged:
            return (jax.lax.dynamic_slice_in_dim(self.k, win_start, W, axis=1),
                    jax.lax.dynamic_slice_in_dim(self.v, win_start, W, axis=1))
        ps = self.page_size
        MP = self.pages.shape[1]
        # covering pages: W tokens starting at any in-page offset (up to
        # ps-1) span ceil(W/ps) + 1 logical pages in the worst case — NOT
        # W//ps + 1, which under-covers whenever W % ps != 0 and the offset
        # is large (regression: tests/test_kvstore.py window sweep)
        npg = min(-(-W // ps) + 1, MP)
        lp0 = jnp.clip(win_start // ps, 0, MP - npg)
        pg = jax.lax.dynamic_slice_in_dim(self.pages, lp0, npg, axis=1)
        P = self.k.shape[0]
        ok = (pg >= 0)[..., None, None, None]
        pgc = jnp.clip(pg, 0, P - 1)
        kw = jnp.where(ok, self.k[pgc], 0)                        # (B,npg,ps,H,D)
        vw = jnp.where(ok, self.v[pgc], 0)
        B = kw.shape[0]
        kw = kw.reshape(B, npg * ps, *kw.shape[3:])
        vw = vw.reshape(B, npg * ps, *vw.shape[3:])
        off = win_start - lp0 * ps
        return (jax.lax.dynamic_slice_in_dim(kw, off, W, axis=1),
                jax.lax.dynamic_slice_in_dim(vw, off, W, axis=1))

    def full(self):
        """Materialize the logical (B, max_len, Hkv, Dh) view — the dense
        fallback for whole-cache readers (dense-attention draft layers).
        Unmapped pages read zeros; callers mask by prefix length anyway."""
        if not self.is_paged:
            return self.k, self.v
        P = self.k.shape[0]
        ok = (self.pages >= 0)[..., None, None, None]
        pgc = jnp.clip(self.pages, 0, P - 1)
        kf = jnp.where(ok, self.k[pgc], 0)                        # (B,MP,ps,H,D)
        vf = jnp.where(ok, self.v[pgc], 0)
        B, MP = self.pages.shape
        return (kf.reshape(B, MP * self.page_size, *kf.shape[3:]),
                vf.reshape(B, MP * self.page_size, *vf.shape[3:]))

    # ---- writes
    def write(self, k_new, v_new, start, row_mask=None):
        """Insert (B, T, Hkv, Dh) at position ``start`` (scalar, or (B,) for
        paged). Returns the new (k, v) storage. Paged writes resolve through
        the page table; rows with ``row_mask == False`` (released slots whose
        pages may already belong to someone else) and positions past the
        row's mapped pages are dropped, not clamped. ``row_mask`` is a
        paged-only concept — the dense layout has no page recycling to
        guard, so supplying one is a caller bug and raises rather than being
        silently ignored."""
        if not self.is_paged:
            if row_mask is not None:
                raise ValueError("row_mask is only meaningful for the paged "
                                 "backend; dense writes are never dropped")
            k = jax.lax.dynamic_update_slice_in_dim(
                self.k, k_new.astype(self.k.dtype), start, axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(
                self.v, v_new.astype(self.v.dtype), start, axis=1)
            return k, v
        B, T = k_new.shape[:2]
        start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (B,))
        pos = start[:, None] + jnp.arange(T)                      # (B, T)
        flat = self._phys_flat(pos)
        if row_mask is not None:
            flat = jnp.where(row_mask[:, None], flat, -1)
        P, ps = self.k.shape[0], self.page_size
        kf = self.k.reshape(P * ps, *self.k.shape[2:])
        vf = self.v.reshape(P * ps, *self.v.shape[2:])
        # mode="drop" only discards indices PAST the end — negatives would
        # wrap python-style onto the last page — so invalid writes are
        # redirected to a past-the-end sentinel first
        fidx = jnp.where(flat >= 0, flat, P * ps).reshape(-1)
        kf = kf.at[fidx].set(k_new.reshape((B * T,) + k_new.shape[2:]
                                           ).astype(kf.dtype), mode="drop")
        vf = vf.at[fidx].set(v_new.reshape((B * T,) + v_new.shape[2:]
                                           ).astype(vf.dtype), mode="drop")
        return kf.reshape(self.k.shape), vf.reshape(self.v.shape)


jax.tree_util.register_pytree_node(
    KVView,
    lambda s: ((s.k, s.v, s.pages), None),
    lambda _, ch: KVView(*ch))


def as_view(kv, pages=None) -> KVView:
    """Normalize a raw ``{"k", "v"}`` cache dict (seed call sites) or an
    existing view into a KVView bound to ``pages``."""
    if isinstance(kv, KVView):
        return kv
    return KVView(kv["k"], kv["v"], pages)


# ------------------------------------------------------------------ init
def init_kv(cfg, batch: int, max_len: int, dtype, store: KVStoreConfig):
    """Per-layer K/V storage leaves for one block."""
    if not store.is_paged:
        return {"k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
                "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)}
    ps = store.resolved_page_size(cfg)
    mp = store.logical_pages(max_len, ps)
    P = store.resolved_num_pages(batch, mp)
    return {"k": jnp.zeros((P, ps, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((P, ps, cfg.num_kv_heads, cfg.head_dim), dtype)}


def empty_page_table(batch: int, max_pages: int):
    return jnp.full((batch, max_pages), -1, jnp.int32)


# ------------------------------------------------------------------ structure
def map_segments(segs, f_kv: Callable, f_other: Callable):
    """Apply ``f_kv`` to raw-KV leaves and ``f_other`` to every other cache
    leaf (cmp / recurrent state), preserving the segments structure. This is
    how backend-split treatments (pool leaves have no batch axis; cmp/state
    leaves do) thread through vmap in_axes, admissions, and commits."""
    out = []
    for seg in segs:
        group = []
        for c in seg:
            d = {}
            for key, sub in c.items():
                d[key] = jax.tree.map(f_kv if key == "kv" else f_other, sub)
            group.append(d)
        out.append(tuple(group))
    return out


def kv_cache_bytes(segs) -> int:
    """Raw-KV footprint of a segments pytree (pool or dense leaves) — the
    peak-KV-bytes metric benchmarks report per serving row."""
    total = 0
    for seg in segs:
        for c in seg:
            if "kv" in c:
                total += sum(int(np.prod(a.shape)) * a.dtype.itemsize
                             for a in jax.tree.leaves(c["kv"]))
    return total


# ------------------------------------------------------------------ admission
@functools.partial(jax.jit, donate_argnums=(0,))
def admit_row_paged(batch_segs, row_segs, row, pages_row):
    """Paged counterpart of ``engine.admit_row_segments``: land a freshly
    prefilled single-request cache into batch row ``row``.

    Raw-KV leaves of ``row_segs`` are dense ``(n, 1, S, Hkv, Dh)`` (prefill
    stays dense — one transient request-sized buffer); they are re-blocked
    into logical pages and scattered into the shared pool at the row's
    physical pages (``pages_row`` (MP,), -1 entries dropped). cmp /
    recurrent leaves are written in place at batch row ``row`` exactly like
    the dense admission path. ``batch_segs`` is donated — no copy of other
    rows, and pool pages owned by other rows are untouched by construction
    (the allocator never double-assigns)."""
    def land_kv(pool, dense):
        ps = pool.shape[2]
        n, _, S = dense.shape[:3]
        P = pool.shape[1]
        mp = S // ps
        blocked = dense.reshape((n, mp, ps) + dense.shape[3:])
        # unmapped (-1) entries must go past the end: mode="drop" wraps
        # negatives onto the last page instead of dropping them
        phys = jnp.where(pages_row >= 0, pages_row, P)
        write = lambda p, b: p.at[phys].set(b.astype(p.dtype), mode="drop")
        return jax.vmap(write)(pool, blocked)

    def land_row(b, s):
        return jax.lax.dynamic_update_slice_in_dim(
            b, s.astype(b.dtype), row, axis=1)

    return map_segments2(batch_segs, row_segs, land_kv, land_row)


def map_segments2(segs_a, segs_b, f_kv, f_other):
    """Two-tree variant of ``map_segments`` (same structure on both sides)."""
    out = []
    for seg_a, seg_b in zip(segs_a, segs_b):
        group = []
        for ca, cb in zip(seg_a, seg_b):
            d = {}
            for key in ca:
                fn = f_kv if key == "kv" else f_other
                d[key] = jax.tree.map(fn, ca[key], cb[key])
            group.append(d)
        out.append(tuple(group))
    return out


# ------------------------------------------------------------------ allocator
class PageAllocator:
    """Host-side free-list page allocator.

    Invariants (property-tested in tests/test_kvstore.py):
      * a page is owned by at most one allocation at a time;
      * ``alloc`` returns ``None`` — and changes nothing — when the pool
        cannot satisfy the request (callers keep the request queued);
      * ``free`` rejects pages that are not currently allocated (double-free
        and foreign-page bugs surface as errors, not silent corruption).
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, -1, -1))  # pop() -> 0,1,2,...
        self._allocated: set = set()

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._allocated)

    @property
    def occupancy(self) -> float:
        return len(self._allocated) / self.num_pages

    def can_alloc(self, n: int) -> bool:
        return 0 < n <= len(self._free)

    def alloc(self, n: int) -> Optional[np.ndarray]:
        """n physical pages, or None (state unchanged) if the pool is
        exhausted — admission then leaves the request pending."""
        if n < 1:
            raise ValueError(f"alloc of {n} pages")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._allocated.update(pages)
        return np.asarray(pages, np.int32)

    def free(self, pages: Sequence[int]) -> None:
        pages = [int(p) for p in np.asarray(pages).reshape(-1)]
        for p in pages:
            if p not in self._allocated:
                raise ValueError(f"freeing page {p} that is not allocated")
        for p in pages:
            self._allocated.remove(p)
            self._free.append(p)

