#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell>] [--reference]

For each cell (all by default): the target's and the draft's prefill at
every prompt bucket of its mix, the paged admission write, the fused step
at the cell's slot count, and with ``--reference`` the plain reference at
the cell's longest sequence. Prints each program's ``memory_analysis()``
and the sum the cell holds at once: weights, the page pools and the
largest temporaries. Nothing runs; a compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
GIB = 2 ** 30


def _bytes(tree) -> int:
    import jax
    return sum(int(a.size) * a.dtype.itemsize for a in jax.tree.leaves(tree))


def rehearse(cell: str, with_reference: bool) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, traffic
    from repro.core import engine as engine_lib
    from repro.core import kvstore
    from repro.models import model

    bm = harness.load_benchmark()
    w = harness.workload(bm, cell)
    cfg = harness.load_config(w["config"])
    mix = traffic.load_mix(w["traffic"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    tcfg = harness.model_config(cfg)
    dcfg = harness.draft_model_config(cfg, tcfg)
    slots, max_ctx = int(mix["slots"]), int(mix["max_context"])
    key = jax.random.PRNGKey(0)
    tp = jax.tree.map(spec, jax.eval_shape(lambda: model.init(key, tcfg)))
    dp = jax.tree.map(spec, jax.eval_shape(lambda: model.init(key, dcfg)))
    store = kvstore.KVStoreConfig("paged", cfg["nsa"]["sel_block"], 0)
    max_pages = max_ctx // store.page_size
    n_pages = slots * max_pages
    store = kvstore.KVStoreConfig("paged", store.page_size, n_pages)
    segs = lambda c: jax.tree.map(spec, jax.eval_shape(
        lambda: model.init_caches(c, slots, max_ctx, store)["segments"]))
    t_segs, d_segs = segs(tcfg), segs(dcfg)
    held = {"target weights": _bytes(tp), "draft weights": _bytes(dp),
            "target pool + cmp": _bytes(t_segs), "draft pool": _bytes(d_segs)}
    print(f"== {cell}: {cfg['name']} x {w['traffic']}, {slots} slots, "
          f"max_context {max_ctx}, {n_pages} pages")
    for k, v in held.items():
        print(f"  {k}: {v / GIB:.3f} GiB")
    temps = {}

    def show(name, compiled):
        ma = compiled.memory_analysis()
        temps[name] = ma.temp_size_in_bytes
        print(f"  {name}: args {ma.argument_size_in_bytes / GIB:.3f} GiB, "
              f"out {ma.output_size_in_bytes / GIB:.3f} GiB, "
              f"temp {ma.temp_size_in_bytes / GIB:.3f} GiB", flush=True)

    for b in traffic.prompt_buckets(mix):
        tok = jax.ShapeDtypeStruct((1, b - 1), jnp.int32, sharding=one)
        for nm, c, p in (("target", tcfg, tp), ("draft", dcfg, dp)):
            show(f"prefill {nm} {b - 1}",
                 engine_lib.jit_prefill(c, max_ctx).lower(p, tok).compile())
    row = lambda c: jax.tree.map(spec, jax.eval_shape(
        lambda: model.prefill(model.init(key, c), c,
                              jnp.zeros((1, 63), jnp.int32), max_ctx)[1]["segments"]))
    ivec = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    prow = jax.ShapeDtypeStruct((max_pages,), jnp.int32, sharding=one)
    show("admit row (target)", kvstore.admit_row_paged.lower(
        t_segs, row(tcfg), ivec, prow).compile())
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=one)
    step = engine_lib.jit_batched_step(tcfg, dcfg, harness.strategy(cfg), True,
                                       0.0, store)
    args = [tp, dp, t_segs, vec(jnp.int32), d_segs, vec(jnp.int32),
            jax.ShapeDtypeStruct((slots, max_pages), jnp.int32, sharding=one),
            vec(jnp.int32), vec(jnp.bool_), vec(jnp.bool_), vec(jnp.int32),
            vec(jnp.int32)]
    compiled = step.lower(*args).compile()
    show(f"fused step x{slots}", compiled)
    print(f"  fused step holds a Pallas kernel: "
          f"{'tpu_custom_call' in compiled.as_text()}")
    if with_reference:
        from bench import check
        from bench.references import nsa_decoder
        S = check._pad(max(traffic.prompt_buckets(mix)) + 256, check.PAD)
        tok = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one)
        at = jax.ShapeDtypeStruct((256,), jnp.int32, sharding=one)
        c_items = nsa_decoder._freeze({k: cfg[k] for k in nsa_decoder.MODEL_KEYS
                                       if k in cfg})
        show(f"reference S={S}", nsa_decoder._logits.lower(
            tp, tok, tok, at, c_items=c_items, fp8=False, block_q=256).compile())
    total = sum(held.values()) + max(temps.values())
    print(f"  held {sum(held.values()) / GIB:.3f} GiB + largest temp "
          f"{max(temps.values()) / GIB:.3f} GiB = {total / GIB:.3f} GiB of 16")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(CHECKOUT))
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness
    cells = args.workload or [w["name"] for w in harness.load_benchmark()["workloads"]]
    for cell in cells:
        rehearse(cell, args.reference)
    return 0


if __name__ == "__main__":
    sys.exit(main())
