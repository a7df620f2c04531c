"""The dropless expert layer on the launch paths' mesh: prefill under pjit
and ``nsa_sharded.decode_step_sharded``, with the launch shardings
(``launch/sharding.py``), match the same program on one device, and no
program gathers an expert weight whole. Run in an 8-device subprocess (the
main test process keeps a single CPU device)."""
from tests.test_distributed import run_sub


def test_mesh_serving_paths_keep_expert_weights_sharded():
    out = run_sub("""
        import re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import obs
        from repro.config import ModelConfig, MoEConfig, NSAConfig
        from repro.models import model, nsa_sharded
        from repro.launch import sharding as shd
        from repro.launch.mesh import make_test_mesh

        E, d, f = 8, 64, 96
        cfg = ModelConfig(
            name="moe-mesh", num_layers=2, d_model=d, num_heads=4,
            num_kv_heads=2, d_ff=128, vocab_size=128, max_seq_len=512,
            dtype="float32", attention="nsa", block_pattern=("moe",),
            nsa=NSAConfig(cmp_block=8, cmp_stride=4, sel_block=16,
                          n_selected=4, window=32),
            moe=MoEConfig(num_experts=E, top_k=2, d_expert=f))
        params = model.init(jax.random.PRNGKey(0), cfg)
        mesh = make_test_mesh(2, 4)
        specs = shd.param_specs(cfg, params, mesh)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
        ps = jax.tree.map(jax.device_put, params, sh)
        w_up = ps["segments"][0][0]["ffn"]["w_up"]
        assert w_up.shape == (2, E, d, f)
        assert w_up.addressable_shards[0].data.shape == (2, E, d // 2, f // 4)

        whole = re.compile(r"\\[(2,)?%d,(%d,%d|%d,%d)\\]" % (E, d, f, f, d))

        def gathers_expert_weights(compiled):
            return [l for l in compiled.as_text().splitlines()
                    if "all-gather(" in l and whole.search(l.split("=")[1])]

        # prefill, as launch/specs.py builds it
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
        h1, _ = model.prefill(params, cfg, toks, 96)
        constrain = shd.activation_constraint(mesh)
        with mesh:
            fn = jax.jit(lambda p, t: model.prefill(p, cfg, t, 96,
                                                    constrain=constrain)[0])
            t_s = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
            obs.reset()
            compiled = fn.lower(ps, t_s).compile()
            assert obs.counters().get("moe.dispatch.dropless", 0) >= 1
            h2 = compiled(ps, t_s)
        assert not gathers_expert_weights(compiled), gathers_expert_weights(compiled)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h1),
                                   rtol=1e-4, atol=1e-4)

        # batch-1 long-context decode, sequence over every axis
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 200), 0, 128)
        _, caches = model.prefill(params, cfg, prompt, max_len=264)
        nxt = jnp.asarray([[5]], jnp.int32)
        one = make_test_mesh(1, 1)
        with one:
            l1 = nsa_sharded.decode_step_sharded(params, cfg, one, caches, nxt,
                                                 tuple(one.axis_names))[0]
        c_specs = shd.cache_specs(cfg, caches, mesh, shard_sequence=True)
        c_s = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                           caches, c_specs, is_leaf=lambda x: isinstance(x, P))
        with mesh:
            fn = jax.jit(lambda p, c, t: nsa_sharded.decode_step_sharded(
                p, cfg, mesh, c, t, tuple(mesh.axis_names))[0])
            compiled = fn.lower(ps, c_s, nxt).compile()
            l2 = compiled(ps, c_s, nxt)
        assert not gathers_expert_weights(compiled), gathers_expert_weights(compiled)
        np.testing.assert_allclose(np.asarray(l2), np.asarray(l1),
                                   rtol=1e-3, atol=1e-3)
        print("MOE_MESH_OK")
    """)
    assert "MOE_MESH_OK" in out
