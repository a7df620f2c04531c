"""Refresh/reuse schedule calibration + draft tree expansion + data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.config import ModelConfig, NSAConfig
from repro.core import draft as draft_lib
from repro.core import engine as engine_lib
from repro.core import planner as planner_lib
from repro.core.schedule import greedy_calibrate, kl_divergence
from repro.core.tree import build_topology
from repro.models import model


def test_kl_divergence_properties():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(4, 16))
    assert kl_divergence(a, a) < 1e-12
    b = rng.normal(size=(4, 16))
    assert kl_divergence(a, b) > 0


def test_greedy_calibrate_synthetic():
    """Layers have known per-layer KL costs; the calibrator must pick the
    cheap ones first and respect the budget."""
    rng = np.random.default_rng(0)
    base = rng.normal(size=(8, 32))
    cost = {1: 0.001, 2: 0.5, 3: 0.002, 4: 0.003, 5: 0.6, 6: 0.004, 7: 0.7}

    def eval_fn(schedule):
        out = base.copy()
        for l in schedule:
            noise = np.random.default_rng(l).normal(size=base.shape)
            out = out + cost[l] * noise
        return out

    sched = greedy_calibrate(eval_fn, num_layers=8, kl_budget=0.01)
    assert 0 not in sched                       # layer 0 never a candidate
    assert set(sched) <= {1, 3, 4, 6}           # only the cheap layers
    assert len(sched) >= 2


def test_greedy_calibrate_max_reuse():
    def eval_fn(schedule):
        return np.zeros((2, 8))                 # zero KL for everything
    sched = greedy_calibrate(eval_fn, num_layers=6, kl_budget=1.0, max_reuse=2)
    assert len(sched) == 2


@pytest.fixture(scope="module")
def tiny_pair():
    cfg = ModelConfig(name="d", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=64,
                      max_seq_len=256, dtype="float32", attention="nsa",
                      nsa=NSAConfig(cmp_block=8, cmp_stride=4, sel_block=16,
                                    n_selected=4, window=32))
    dcfg = draft_lib.draft_config(cfg, num_layers=1)
    tp = model.init(jax.random.PRNGKey(0), cfg)
    dp = model.init(jax.random.PRNGKey(1), dcfg)
    return tp, cfg, dp, dcfg


def test_expand_tree_structure(tiny_pair):
    """Children tokens are the ranked top-k of the PARENT's draft logits,
    and node_q rows are valid distributions."""
    tp, cfg, dp, dcfg = tiny_pair
    toks = jnp.asarray(np.arange(24) % 64)[None]
    _, dcaches = model.prefill(dp, dcfg, toks[:, :-1], max_len=128)
    topo = build_topology(2, 2, "bfs")
    verify = engine_lib.jit_verify(dcfg, None)
    tokens, node_q, _ = draft_lib.expand_tree(
        lambda caches, tk, pos, tm, par: verify(dp, caches, tk, pos, tm, par),
        dcfg, dcaches, topo, jnp.asarray([int(toks[0, -1])], jnp.int32))
    tokens = np.asarray(tokens[0])
    q = np.asarray(node_q[0])
    assert tokens[0] == int(toks[0, -1])        # pending root preserved
    ranks = draft_lib.sibling_ranks(topo)
    for i in range(1, topo.num_nodes):
        p = int(topo.parents[i])
        topk = np.argsort(-q[p])[: ranks[i] + 1]
        assert tokens[i] == topk[ranks[i]]
    np.testing.assert_allclose(q.sum(-1), 1.0, rtol=1e-4)


def _planted_logits(vocab: int, kmax: int) -> jnp.ndarray:
    """(2, 3, vocab) bf16 draft logits with the orders top-k must keep:
    ties at the maximum, a tie across the kmax-th place, -inf entries, a
    row with fewer finite entries than kmax and a row all -inf."""
    rng = np.random.default_rng(vocab + kmax)
    x = rng.normal(size=(2, 3, vocab)).astype(np.float32)
    x[..., rng.integers(0, vocab, size=vocab // 16)] = -np.inf
    # row (0, 0): five entries tie at the maximum, the last index among them
    x[0, 0, [vocab - 1, 7, 4096, 11, 30000]] = 9.0
    # row (0, 1): kmax - 1 distinct leaders, then a four-way tie at the
    # kmax-th place straddling them in index order
    x[0, 1, vocab - kmax:] = 9.0 + np.arange(kmax)
    x[0, 1, [vocab - kmax - 1, 3, 2 * kmax + 5, vocab // 2]] = 9.0
    # row (0, 2): three finite entries, the rest -inf
    x[0, 2] = -np.inf
    x[0, 2, [vocab - 2, 1, vocab // 3]] = [1.0, 1.0, -2.0]
    # row (1, 0): all -inf
    x[1, 0] = -np.inf
    return jnp.asarray(x, jnp.bfloat16)


@pytest.mark.parametrize("vocab", [32768, 151936])
@pytest.mark.parametrize("kmax", [1, 2, 4, 8, 10])
def test_top_k_indices_equal_lax_top_k(vocab, kmax):
    """The k max-reductions pick exactly lax.top_k's indices, in its order,
    on bf16 logits with ties and -inf rows."""
    x = _planted_logits(vocab, kmax)
    want = np.asarray(jax.lax.top_k(x, kmax)[1])
    got = np.asarray(jax.jit(draft_lib.top_k_indices, static_argnums=1)(x, kmax))
    np.testing.assert_array_equal(got, want)
    assert all(len(set(r)) == kmax for r in got.reshape(-1, kmax))


def _expand_tree_sorting_all_rows(verify_fn, draft_caches, topo, pending_token,
                                  temperature):
    """expand_tree as it was: lax.top_k over every node's row at each level."""
    B, T = pending_token.shape[0], topo.num_nodes
    positions = jnp.asarray(topo.depths)[None] + draft_caches["length"]
    positions = jnp.broadcast_to(positions, (B, T)).astype(jnp.int32)
    tmask = jnp.broadcast_to(jnp.asarray(topo.mask)[None], (B, T, T))
    parents = jnp.asarray(topo.parents)
    tokens = jnp.zeros((B, T), jnp.int32).at[:, 0].set(pending_token)
    rank = draft_lib.sibling_ranks(topo)
    maxd = int(topo.depths.max())
    for d in range(maxd + 1):
        logits, _ = verify_fn(draft_caches, tokens, positions, tmask, parents)
        scaled = logits.astype(jnp.float32)
        if temperature > 0:
            scaled = scaled / temperature
        node_q = jax.nn.softmax(scaled, axis=-1)
        if d == maxd:
            break
        level = np.where(topo.depths == d + 1)[0]
        _, topk_idx = jax.lax.top_k(logits, int(rank[level].max()) + 1)
        picked = topk_idx[:, topo.parents[level], rank[level]]
        tokens = tokens.at[:, level].set(picked)
    return tokens, node_q


_SHAPES = sorted({(s.tree_depth, s.tree_width, s.tree_budget, s.traversal)
                  for s in planner_lib.candidate_strategies("Strict", 4)})


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("depth,width,budget,order", _SHAPES)
def test_expand_tree_matches_sorting_every_row(tiny_pair, depth, width,
                                               budget, order, temperature):
    """Selecting from the parent rows by max-reductions gives the tokens and
    node_q of a lax.top_k over every node's row, for every planner shape."""
    _, _, dp, dcfg = tiny_pair
    toks = jnp.asarray((np.arange(10) * 7) % 64)[None]
    _, dcaches = model.prefill(dp, dcfg, toks[:, :-1], max_len=16)
    topo = build_topology(depth, width, order, budget)
    verify = engine_lib.jit_verify(dcfg, None)
    fn = lambda caches, tk, pos, tm, par: verify(dp, caches, tk, pos, tm, par)
    pending = jnp.asarray([int(toks[0, -1])], jnp.int32)
    obs.reset()
    tokens, node_q, _ = draft_lib.expand_tree(fn, dcfg, dcaches, topo, pending,
                                              temperature=temperature)
    ref_tokens, ref_q = _expand_tree_sorting_all_rows(fn, dcaches, topo,
                                                      pending, temperature)
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(ref_tokens))
    np.testing.assert_array_equal(np.asarray(node_q), np.asarray(ref_q))
    if (depth, width, budget) == (4, 2, 0):     # the benchmark cells' tree
        c = obs.counters()
        assert (c["draft.topk.rows"], c["draft.topk.passes"]) == (15, 8)


def test_prefetch_iterator():
    from repro.data import PrefetchIterator, SyntheticConfig, SyntheticCorpus, token_stream
    c = SyntheticCorpus(SyntheticConfig(vocab_size=64))
    it = token_stream(c, batch_size=2, seq_len=16)
    pf = PrefetchIterator(it, depth=2)
    steps = []
    for _ in range(3):
        step, batch = next(pf)
        steps.append(step)
        assert batch.shape == (2, 16)
    assert steps == [0, 1, 2]
    pf.close()
