"""The serving paths' dropless expert layer (models/moe.py).

Every (token, held expert) assignment is computed, whatever the load, so
the layer equals the per-token oracle and a token's output never depends
on the other tokens in the call; the shares that the chips of an
expert-parallel deployment hold add up to the uncut layer; and serving
(prefill, then decode and tree verify through the paged cache) agrees with
a full forward pass over each node's path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.config import ModelConfig, MoEConfig, NSAConfig, ServeConfig, SSVConfig
from repro.core import draft as draft_lib
from repro.core import engine as engine_lib
from repro.core import tree as tree_lib
from repro.models import model
from repro.models import moe as moe_lib
from tests.test_moe_recurrent import _moe_cfg, _ref_moe


def _share(cfg, params, first, held, width):
    """The layer one chip holds: ``held`` experts from ``first`` of a
    router ``width`` wide, and their slice of the weights."""
    moe = dataclasses.replace(cfg.moe, num_experts=held, router_experts=width,
                              first_expert=first)
    sliced = {k: (v[first:first + held] if k != "router" else v)
              for k, v in params.items()}
    return dataclasses.replace(cfg, moe=moe), sliced


@pytest.mark.parametrize("B, S, skew", [(2, 16, 0.0), (1, 31, 0.0), (3, 7, 0.0),
                                        (2, 16, 50.0)],
                         ids=["spread", "one-tree", "odd-count", "one-hot-spot"])
def test_dropless_matches_per_token_oracle(B, S, skew):
    # skew: a router column so large that every token picks expert 0, with
    # a capacity the training dispatch would overflow
    cfg = _moe_cfg(cf=0.25)
    key = jax.random.PRNGKey(0)
    params = moe_lib.moe_init(key, cfg)
    params["router"] = params["router"].at[:, 0].add(skew)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, 32))
    obs.reset()
    got, aux = moe_lib.moe_apply_dropless(params, cfg, x)
    np.testing.assert_allclose(np.asarray(got), _ref_moe(params, cfg, x),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) == 0.0
    assert obs.counters().get("moe.dispatch.dropless") == 1
    assert "moe.dispatch.capacity" not in obs.counters()


@pytest.mark.parametrize("activation, shared", [("swiglu", 1), ("gelu", 0)])
def test_dropless_equals_capacity_dispatch_when_nothing_drops(activation, shared):
    cfg = _moe_cfg(cf=100.0)
    cfg = dataclasses.replace(cfg, activation=activation, moe=dataclasses.replace(
        cfg.moe, num_shared_experts=shared))
    key = jax.random.PRNGKey(2)
    params = moe_lib.moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 9, 32))
    np.testing.assert_allclose(np.asarray(moe_lib.moe_apply_dropless(params, cfg, x)[0]),
                               np.asarray(moe_lib.moe_apply(params, cfg, x)[0]),
                               rtol=1e-4, atol=1e-5)


def test_shares_sum_to_the_uncut_layer():
    cfg = _moe_cfg(E=16, k=4)
    key = jax.random.PRNGKey(3)
    params = moe_lib.moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 12, 32))
    whole, _ = moe_lib.moe_apply_dropless(params, cfg, x)
    parts = []
    for first in (0, 4, 8, 12):
        c, p = _share(cfg, params, first, 4, 16)
        assert p["router"].shape == (32, 16) and p["w_up"].shape[0] == 4
        parts.append(moe_lib.moe_apply_dropless(p, c, x)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    # a share is the held experts' part, not the whole
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-2


def test_capacity_dispatch_refuses_a_share():
    cfg = _moe_cfg(E=16, k=4)
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    c, p = _share(cfg, params, 4, 4, 16)
    with pytest.raises(ValueError, match="dropless"):
        moe_lib.moe_apply(p, c, jnp.ones((1, 4, 32)))


def _tiny(attention="dense"):
    """Two expert layers holding 4 of 16 experts (from expert 4), top-4."""
    return ModelConfig(
        name="moe-share", num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, vocab_size=97, max_seq_len=256, dtype="float32",
        attention=attention, block_pattern=("moe",),
        nsa=NSAConfig(cmp_block=8, cmp_stride=4, sel_block=16, n_selected=4,
                      window=32),
        moe=MoEConfig(num_experts=4, router_experts=16, first_expert=4, top_k=4,
                      d_expert=48))


def test_verify_rows_are_independent():
    """Mapped over rows as the fused step maps them, the dropless layer's
    einsums batch over the rows; changing one row's tokens leaves the other
    row's node logits where they were."""
    cfg = _tiny("nsa")
    key = jax.random.PRNGKey(5)
    params = model.init(key, cfg)
    topo = tree_lib.build_topology(2, 2)
    T = topo.num_nodes
    prompts = jax.random.randint(key, (2, 1, 40), 0, cfg.vocab_size)
    caches = [model.prefill(params, cfg, prompts[r], max_len=96)[1]
              for r in range(2)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *caches)
    positions = jnp.asarray(tree_lib.positions_for(topo, 40))[None]
    mask = jnp.asarray(topo.mask)[None]
    parents = jnp.asarray(topo.parents)

    @jax.jit
    def rows(tokens):
        return jax.vmap(lambda c, t: model.verify_step(
            params, cfg, c, t[None], positions, mask, parents)[0][0])(stacked, tokens)

    obs.reset()
    a = jax.random.randint(jax.random.fold_in(key, 1), (2, T), 0, cfg.vocab_size)
    b = a.at[1].set((a[1] * 7 + 3) % cfg.vocab_size)
    la, lb = rows(a), rows(b)
    assert obs.counters().get("moe.dispatch.dropless", 0) >= 1
    np.testing.assert_allclose(np.asarray(la[0]), np.asarray(lb[0]),
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(la[1] - lb[1]).max()) > 1e-3


def test_serving_through_the_paged_cache_agrees_with_a_full_forward():
    """A prompt admitted into the paged engine (prefill), then a decode
    step and a draft tree verified against its pages: every node's logits
    equal those of one full forward pass over the prompt and that node's
    path."""
    cfg = _tiny("dense")
    dcfg = draft_lib.draft_config(cfg, num_layers=1)
    key = jax.random.PRNGKey(7)
    tp, dp = model.init(key, cfg), model.init(jax.random.fold_in(key, 1), dcfg)
    serve = ServeConfig(max_batch=2, max_new_tokens=8, temperature=0.0,
                        max_context=128, ssv=SSVConfig(tree_depth=2, tree_width=2),
                        use_planner=False, kv_backend="paged", kv_page_size=16)
    eng = engine_lib.BatchedSSVEngine(tp, cfg, dp, dcfg, serve)
    eng.start_empty(2)
    prompt = np.asarray(jax.random.randint(key, (37,), 0, cfg.vocab_size))
    eng.admit(1, prompt, max_new_tokens=8)
    n0 = len(prompt) - 1                       # committed; the last is the root

    def full(seq):
        h, _ = model.prefill(tp, cfg, jnp.asarray(seq, jnp.int32)[None],
                             max_len=len(seq))
        return np.asarray(model.logits_fn(tp, cfg, h)[0])

    def verify(topo, tokens):
        row = paged_row(eng, 1, n0)
        positions = jnp.asarray(tree_lib.positions_for(topo, n0))[None]
        logits, _ = model.verify_step(tp, cfg, row, jnp.asarray(tokens)[None],
                                      positions, jnp.asarray(topo.mask)[None],
                                      jnp.asarray(topo.parents))
        return np.asarray(logits[0])

    # decode: the root alone
    got = verify(tree_lib.chain_topology(0), prompt[-1:])
    np.testing.assert_allclose(got[0], full(prompt)[-1], rtol=2e-3, atol=2e-3)
    # a tree: each node against the forward pass over its path
    topo = tree_lib.build_topology(2, 2)
    rng = np.random.default_rng(0)
    tokens = np.concatenate([prompt[-1:], rng.integers(0, cfg.vocab_size,
                                                       topo.num_nodes - 1)])
    got = verify(topo, tokens.astype(np.int32))
    for i in range(topo.num_nodes):
        path, j = [], i
        while j >= 0:
            path.append(int(tokens[j]))
            j = int(topo.parents[j])
        seq = np.concatenate([prompt[:-1], path[::-1]])
        np.testing.assert_allclose(got[i], full(seq)[-1], rtol=2e-3, atol=2e-3)
    # the fused step over the same pages emits the greedy token first
    toks, _ = eng.step(np.asarray([False, True]))
    assert int(toks[1, 0]) == int(full(prompt)[-1].argmax())


def paged_row(eng, slot, length):
    """Row ``slot`` of the engine's paged caches at committed ``length``,
    as the fused step hands one row to ``model.verify_step``."""
    from repro.core import kvstore
    segs = kvstore.map_segments(eng.t_segs, lambda a: a,
                                lambda a: a[:, slot:slot + 1])
    return {"segments": segs, "length": jnp.int32(length),
            "pages": jnp.asarray(eng.pages[slot])[None]}
