"""KVCacheStore subsystem tests (repro.core.kvstore).

Property-style allocator invariants (hypothesis when installed, seeded
parametrized sweep otherwise — the PR-2/PR-3 shim pattern): no page
double-assignment, clean failure (state unchanged, queue keeps pending) on
exhaustion, everything freed on request completion, double-free rejected.

View-layer contracts: paged reads/writes resolve through the page table and
match the dense layout bit-for-bit; adversarial selected-block indices
(negative / out-of-range / unmapped) read an explicit zero page and are
masked out of NSA attention — never silently clamped onto a neighbor block
or another request's pages.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.config import ModelConfig, NSAConfig
from repro.core import kvstore as KS
from repro.core import schedule as S
from repro.models import nsa as nsa_lib


def seeded_property(n_examples=30, seed_max=10_000):
    if HAVE_HYPOTHESIS:
        def deco(fn):
            return settings(max_examples=n_examples, deadline=None)(
                given(seed=st.integers(0, seed_max))(fn))
        return deco

    def deco(fn):
        return pytest.mark.parametrize("seed", range(n_examples))(fn)
    return deco


# ------------------------------------------------------------------ allocator
@seeded_property()
def test_allocator_never_double_assigns(seed):
    """Across a random alloc/free interleave, live allocations are disjoint
    and every page id stays within the pool."""
    rng = np.random.default_rng(seed)
    total = int(rng.integers(4, 40))
    alloc = KS.PageAllocator(total)
    live = {}
    next_id = 0
    for _ in range(200):
        if rng.random() < 0.55:
            n = int(rng.integers(1, 6))
            pg = alloc.alloc(n)
            if pg is None:
                assert n > alloc.free_count     # only fails when short
                continue
            assert len(pg) == n
            flat = [p for ps in live.values() for p in ps]
            assert not set(pg.tolist()) & set(flat), "page double-assigned"
            assert all(0 <= p < total for p in pg.tolist())
            live[next_id] = pg.tolist()
            next_id += 1
        elif live:
            rid = list(live)[int(rng.integers(0, len(live)))]
            alloc.free(live.pop(rid))
        assert alloc.free_count + alloc.used_count == total
    for ps in live.values():
        alloc.free(ps)
    assert alloc.free_count == total and alloc.used_count == 0


@seeded_property(n_examples=15)
def test_allocator_exhaustion_is_clean(seed):
    """An alloc the pool cannot satisfy returns None and changes nothing —
    the caller's queue keeps the request pending."""
    rng = np.random.default_rng(seed)
    total = int(rng.integers(2, 10))
    alloc = KS.PageAllocator(total)
    held = alloc.alloc(total - 1)
    free_before = alloc.free_count
    assert alloc.alloc(2) is None
    assert alloc.free_count == free_before
    assert alloc.can_alloc(1) and not alloc.can_alloc(2)
    alloc.free(held)
    assert alloc.free_count == total


def test_allocator_rejects_double_free_and_foreign_pages():
    alloc = KS.PageAllocator(4)
    pg = alloc.alloc(2)
    alloc.free(pg)
    with pytest.raises(ValueError, match="not allocated"):
        alloc.free(pg)
    other = alloc.alloc(1)
    with pytest.raises(ValueError, match="not allocated"):
        alloc.free([3] if int(other[0]) != 3 else [2])
    with pytest.raises(ValueError):
        KS.PageAllocator(0)
    with pytest.raises(ValueError):
        alloc.alloc(0)


# ------------------------------------------------------------------ view layer
def _paged_twin(rng, B=2, S=64, H=2, D=8, ps=16, extra_pages=3, perm_seed=0):
    """A dense view and a paged view holding identical logical contents,
    with a shuffled physical page assignment (the realistic case)."""
    k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    mp = S // ps
    P = B * mp + extra_pages
    order = np.random.default_rng(perm_seed).permutation(P)[: B * mp]
    pages = order.reshape(B, mp).astype(np.int32)
    poolk = jnp.zeros((P, ps, H, D), jnp.float32)
    poolv = jnp.zeros((P, ps, H, D), jnp.float32)
    for b in range(B):
        poolk = poolk.at[pages[b]].set(np.asarray(k[b]).reshape(mp, ps, H, D))
        poolv = poolv.at[pages[b]].set(np.asarray(v[b]).reshape(mp, ps, H, D))
    return (KS.KVView(k, v),
            KS.KVView(poolk, poolv, jnp.asarray(pages)))


def _logical_blocks(dense, idx, sel_block):
    """Plain-loop selected-block gather on the dense logical cache: idx
    (B, T, H, n) -> (B, T, H, n, sel_block, D); a block wholly or partly
    outside [0, S) reads zeros where it leaves the cache."""
    k, v = np.asarray(dense.k), np.asarray(dense.v)
    S, D = k.shape[1], k.shape[3]
    idx = np.asarray(idx)
    out_k = np.zeros(idx.shape + (sel_block, D), k.dtype)
    out_v = np.zeros_like(out_k)
    for i in np.ndindex(*idx.shape):
        b, h = i[0], i[2]
        for j in range(sel_block):
            t = idx[i] * sel_block + j
            if 0 <= t < S:
                out_k[i + (j,)] = k[b, t, h]
                out_v[i + (j,)] = v[b, t, h]
    return out_k, out_v


@seeded_property(n_examples=10)
def test_view_read_paths_match_dense(seed):
    rng = np.random.default_rng(seed)
    dense, paged = _paged_twin(rng, perm_seed=seed)
    assert paged.is_paged and paged.max_len == dense.max_len
    np.testing.assert_array_equal(np.asarray(paged.full()[0]),
                                  np.asarray(dense.k))
    tok = jnp.asarray(rng.integers(-5, dense.max_len + 5, size=(2, 9)), jnp.int32)
    for a, b in zip(dense.gather_tokens(tok), paged.gather_tokens(tok)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # window lengths that do and do not divide the page size, at offsets
    # spanning the whole page (ws=15 with W%ps=8 is the regression case: a
    # one-page-short cover slid the window by a token)
    for W in (16, 24):
        for ws in (0, 3, 9, 15, 17, 31, 40):
            for a, b in zip(dense.window(jnp.int32(ws), W),
                            paged.window(jnp.int32(ws), W)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    idx = jnp.asarray(rng.integers(-3, 7, size=(2, 4, 2, 3)), jnp.int32)
    for a, b in zip(dense.gather_blocks(idx, 16), paged.gather_blocks(idx, 16)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_view_writes_match_dense_and_respect_masks(rng):
    dense, paged = _paged_twin(rng)
    kn = jnp.asarray(rng.normal(size=(2, 5, 2, 8)).astype(np.float32))
    vn = jnp.asarray(rng.normal(size=(2, 5, 2, 8)).astype(np.float32))
    dk, _ = dense.write(kn, vn, 10)
    pk, pv = paged.write(kn, vn, jnp.full((2,), 10), row_mask=jnp.array([True, True]))
    np.testing.assert_array_equal(
        np.asarray(KS.KVView(pk, pv, paged.pages).full()[0]), np.asarray(dk))
    # masked row writes are dropped — its pages (possibly re-owned by another
    # request by now) keep their bytes
    pk2, pv2 = paged.write(kn, vn, jnp.full((2,), 10),
                           row_mask=jnp.array([True, False]))
    after = np.asarray(KS.KVView(pk2, pv2, paged.pages).full()[0])
    np.testing.assert_array_equal(after[1], np.asarray(dense.k[1]))
    np.testing.assert_array_equal(after[0], np.asarray(dk[0]))
    # out-of-capacity positions are dropped, not clamped onto the last page
    before = np.asarray(paged.k)
    pk3, _ = paged.write(kn, vn, jnp.full((2,), paged.max_len - 2),
                         row_mask=jnp.array([True, True]))
    assert np.asarray(pk3).shape == before.shape   # no error, partial drop


@pytest.mark.parametrize("case", ["page_is_block", "page_is_two_blocks",
                                  "unmapped_pages", "negative_blocks",
                                  "past_the_end_blocks"])
def test_gather_blocks_slabs_match_logical_gather(case):
    """The paged slab gather returns exactly what a plain gather of the
    dense logical cache returns, for pages of one or two selection blocks;
    unmapped pages and negative / past-the-end block indices read zeros."""
    rng = np.random.default_rng(3)
    sel_block = 8
    ps = 2 * sel_block if case == "page_is_two_blocks" else sel_block
    dense, paged = _paged_twin(rng, S=64, H=3, D=8, ps=ps, perm_seed=4)
    nsb = dense.max_len // sel_block
    lo, hi = {"negative_blocks": (-nsb, 0),
              "past_the_end_blocks": (nsb, 2 * nsb)}.get(case, (0, nsb))
    idx = jnp.asarray(rng.integers(lo, hi, size=(2, 5, 3, 4)), jnp.int32)
    if case == "unmapped_pages":
        # the first and last logical page of row 0 unmapped: the slab gather
        # reads zeros where the dense cache still holds the bytes
        holey = paged.pages.at[0, 0].set(-1).at[0, -1].set(-1)
        paged = KS.KVView(paged.k, paged.v, holey)
        want_k, want_v = _logical_blocks(dense, idx, sel_block)
        lp = np.asarray(idx)[0] // (ps // sel_block)
        hole = (lp == 0) | (lp == 64 // ps - 1)
        want_k[0][hole] = 0.0
        want_v[0][hole] = 0.0
    else:
        want_k, want_v = _logical_blocks(dense, idx, sel_block)
    got_k, got_v = paged.gather_blocks(idx, sel_block)
    assert got_k.shape == want_k.shape
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    if case in ("negative_blocks", "past_the_end_blocks"):
        assert not np.asarray(got_k).any()


@pytest.mark.parametrize("ps", [8, 16])
def test_paged_write_then_gather_tokens_round_trip(ps):
    """A paged write lands each token at pool[page, offset] of its row's
    page and gather_tokens reads the same values back."""
    rng = np.random.default_rng(10 + ps)
    _, paged = _paged_twin(rng, S=64, H=3, D=8, ps=ps, perm_seed=1)
    kn = jnp.asarray(rng.normal(size=(2, 11, 3, 8)).astype(np.float32))
    vn = jnp.asarray(rng.normal(size=(2, 11, 3, 8)).astype(np.float32))
    start = jnp.asarray([ps - 3, 2 * ps + 1], jnp.int32)
    pk, pv = paged.write(kn, vn, start, row_mask=jnp.array([True, True]))
    pages = np.asarray(paged.pages)
    for b in range(2):
        for t in range(11):
            pos = int(start[b]) + t
            np.testing.assert_array_equal(
                np.asarray(pk)[pages[b, pos // ps], pos % ps],
                np.asarray(kn)[b, t])
    tok = start[:, None] + jnp.arange(11)
    back = KS.KVView(pk, pv, paged.pages).gather_tokens(tok)
    np.testing.assert_array_equal(np.asarray(back[0]), np.asarray(kn))
    np.testing.assert_array_equal(np.asarray(back[1]), np.asarray(vn))


# ------------------------------------------------ adversarial selected blocks
def test_gather_blocks_adversarial_indices_read_zero_pages(rng):
    """Out-of-range / negative / unmapped block indices must read an explicit
    zero page (regression: the seed clamped the gather onto block 0 / the
    last block, silently attending the wrong tokens)."""
    dense, paged = _paged_twin(rng)
    nsb = dense.max_len // 16
    bad = jnp.asarray([[[[-1, -7, nsb, nsb + 5]] * 2]], jnp.int32)
    bad = jnp.broadcast_to(bad, (2, 1, 2, 4))
    for view in (dense, paged):
        k_sel, v_sel = view.gather_blocks(bad, 16)
        np.testing.assert_array_equal(np.asarray(k_sel), 0.0)
        np.testing.assert_array_equal(np.asarray(v_sel), 0.0)
    # unmapped logical page (paged only): mapped region ends at max_len
    hole = jnp.concatenate([paged.pages, jnp.full((2, 2), -1, jnp.int32)], axis=1)
    holey = KS.KVView(paged.k, paged.v, hole)
    idx = jnp.full((2, 1, 2, 1), nsb, jnp.int32)   # first hole page
    k_sel, _ = holey.gather_blocks(idx, 16)
    np.testing.assert_array_equal(np.asarray(k_sel), 0.0)


def test_nsa_verify_ref_masks_adversarial_sel_idx(rng):
    """nsa_verify_ref with hostile sel_idx (negative + past-prefix, marked
    valid) must produce exactly the output of the same call with those slots
    marked invalid — adversarial indices can shift no attention mass."""
    NSA = NSAConfig(cmp_block=8, cmp_stride=4, sel_block=16, n_selected=4,
                    window=32)
    cfg = ModelConfig(name="adv", num_layers=1, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=64,
                      dtype="float32", attention="nsa", nsa=NSA)
    params = nsa_lib.nsa_init(jax.random.PRNGKey(0), cfg)
    B, T, S, prefix = 1, 3, 128, 100
    x = jnp.asarray(rng.normal(size=(B, T, cfg.d_model)).astype(np.float32))
    cache = {"k": jnp.asarray(rng.normal(size=(B, S, 2, 16)).astype(np.float32)),
             "v": jnp.asarray(rng.normal(size=(B, S, 2, 16)).astype(np.float32))}
    ncb = (S - NSA.cmp_block) // NSA.cmp_stride + 1
    cmp_cache = {"k_cmp": jnp.asarray(rng.normal(size=(B, ncb, 2, 16)).astype(np.float32)),
                 "v_cmp": jnp.asarray(rng.normal(size=(B, ncb, 2, 16)).astype(np.float32))}
    positions = jnp.asarray(prefix + np.arange(T))[None]
    tm = jnp.asarray(np.tril(np.ones((T, T), bool)))[None]
    good = jnp.asarray(np.sort(rng.integers(0, prefix // 16, (B, T, 2, 4)),
                               axis=-1), jnp.int32)
    valid = jnp.ones((B, T, 2, 4), bool)
    # slots 1 and 3 turn hostile: negative and far-out-of-range
    hostile = good.at[..., 1].set(-3).at[..., 3].set(S // 16 + 9)
    out_hostile = nsa_lib.nsa_verify_ref(params, cfg, x, cache, cmp_cache,
                                         prefix, positions, tm,
                                         sel_idx=hostile, sel_valid=valid,
                                         return_kv=False)
    out_masked = nsa_lib.nsa_verify_ref(params, cfg, x, cache, cmp_cache,
                                        prefix, positions, tm,
                                        sel_idx=hostile,
                                        sel_valid=valid.at[..., 1].set(False)
                                                       .at[..., 3].set(False),
                                        return_kv=False)
    np.testing.assert_array_equal(np.asarray(out_hostile),
                                  np.asarray(out_masked))


# ------------------------------------------------ scheduler page gating
def test_scheduler_page_gate_keeps_queue_pending_until_pages_free():
    """Admission requires free pages, not just a free slot: with the pool
    held, an arrived request stays queued (no exception, no placement); it
    admits as soon as pages free up. FIFO order survives the wait."""
    alloc = KS.PageAllocator(6)
    sched = S.Scheduler(2, pages_for=lambda r: 3,
                        free_pages=lambda: alloc.free_count, total_pages=6)
    hold = alloc.alloc(5)                      # 1 free < 3 needed
    sched.submit(S.Request(req_id=0, prompt=np.arange(4)))
    sched.submit(S.Request(req_id=1, prompt=np.arange(4)))
    assert sched.admit(0.0) == []              # gated, still pending
    assert len(sched.queue) == 2
    assert sched.page_occupancy() == pytest.approx(5 / 6)
    alloc.free(hold[:2])                       # 3 free now
    placed = sched.admit(1.0)
    assert [r.req_id for _, r in placed] == [0]
    alloc.alloc(3)                             # engine takes request 0's pages
    assert sched.admit(1.0) == []              # request 1 still gated
    alloc.free(hold[2:])
    placed = sched.admit(2.0)
    assert [r.req_id for _, r in placed] == [1]


def test_scheduler_page_gate_counts_same_call_reservations():
    """Two free slots, pages for only one request: a single admit() call must
    not place both (pages claimed by the first placement count against the
    second)."""
    alloc = KS.PageAllocator(4)
    sched = S.Scheduler(2, pages_for=lambda r: 3,
                        free_pages=lambda: alloc.free_count, total_pages=4)
    for i in range(2):
        sched.submit(S.Request(req_id=i, prompt=np.arange(4)))
    placed = sched.admit(0.0)
    assert [r.req_id for _, r in placed] == [0]


# ------------------------------------------------ config validation
def test_store_config_validation():
    nsa_cfg = ModelConfig(name="v", num_layers=1, d_model=32, num_heads=2,
                          num_kv_heads=2, d_ff=64, vocab_size=32,
                          attention="nsa",
                          nsa=NSAConfig(cmp_block=8, cmp_stride=4,
                                        sel_block=16, n_selected=4, window=32))
    with pytest.raises(ValueError, match="backend"):
        KS.KVStoreConfig(backend="ragged")
    with pytest.raises(ValueError, match="sel_block"):
        KS.KVStoreConfig("paged", page_size=24).resolved_page_size(nsa_cfg)
    st_cfg = KS.KVStoreConfig("paged")
    assert st_cfg.resolved_page_size(nsa_cfg) == 16
    with pytest.raises(ValueError, match="multiple"):
        st_cfg.logical_pages(100, 16)
    assert st_cfg.logical_pages(256, 16) == 16
    assert KS.pages_needed(0, 16) == 1 and KS.pages_needed(17, 16) == 2
