#!/usr/bin/env python3
"""Smoke test of the SSV serving path on one TPU chip.

    python chip_smoke.py [--seed N]

Runs in one process, on the first TPU device, at the published widths of
``ssv-nsa-1b`` (16 layers, d_model 2048, Hq 32 / Hkv 8, Dh 64, bf16) with
weights drawn from ``--seed``:

  (a) serving: 4 requests with 3073-token prompts over 2 slots through
      ``BatchedSSVEngine.serve_continuous`` on the paged KV store
      (max_context 8192, 32 new tokens each). Every request must finish with
      its full token budget, and the target's logits for one tree-verify
      step on the chip must match the same call on the host CPU in float32.
      Greedy agreement with ``autoregressive_decode`` is reported, not gated.
  (b) kernel: ``nsa_verify_kernel_layer`` (refresh and reuse layers, dense
      and paged KV) compiled for the chip at a 16K context must match
      ``nsa_verify_ref`` on the chip within a bf16 tolerance.

Compile and run seconds of each phase and the compile-cache counters go to
earlier lines. The last line of stdout is one JSON object naming the device,
printed only when every phase passed. Without a TPU the script exits non-zero
before any phase: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

PROMPT_LEN = 3073      # prefill 3072 = 6 chunks of 512; > window + n*sel_block
MAX_CONTEXT = 8192
NEW_TOKENS = 32
REQUESTS, SLOTS = 4, 2
KERNEL_CONTEXT = 16384
# bf16 serving against a float32 host reference: relative L2 error of the
# (T, vocab) logits, and of the kernel layer's output against its reference
LOGITS_REL_L2_TOL = 5e-2
KERNEL_REL_MAX_TOL = 2e-2


def compile_totals():
    """(trace+lower s, backend compile s, cache load s, persistent-cache
    requests, hits) of the process so far, from ``repro.obs``."""
    from repro import obs
    snap = obs.snapshot()
    sec = collections.defaultdict(float)
    for c in snap["compiles"]:
        sec[c.phase] += c.seconds
    cnt = snap["counters"]
    return (sec["trace"] + sec["lower"], sec["compile"], sec["cache_load"],
            cnt.get("compile_cache.requests", 0),
            cnt.get("compile_cache.hits", 0))


def timed_phase(name, fn):
    """Run ``fn``; print its wall, compile and run seconds. Returns
    (ok, result)."""
    before = compile_totals()
    t0 = time.perf_counter()
    try:
        result = fn()
        ok = True
    except Exception:                       # report, keep the other phase
        traceback.print_exc()
        result, ok = None, False
    wall = time.perf_counter() - t0
    tl, bc, cl, req, hit = (a - b for a, b in zip(compile_totals(), before))
    print(f"[{name}] {'PASS' if ok else 'FAIL'}: wall {wall:.2f}s, "
          f"trace+lower {tl:.2f}s, backend compile {bc:.2f}s, cache loads "
          f"{cl:.2f}s, run {max(wall - tl - bc - cl, 0.0):.2f}s; persistent "
          f"cache {hit} hits / {req - hit} misses", flush=True)
    return ok, result


def rel_l2(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def rel_max(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def to_host_f32(tree):
    """Copy a pytree to the host CPU device, floating leaves as float32."""
    import jax
    import numpy as np
    cpu = jax.devices("cpu")[0]

    def one(a):
        a = np.asarray(a)
        if np.issubdtype(a.dtype, np.floating) or a.dtype.name == "bfloat16":
            a = a.astype(np.float32)
        return jax.device_put(a, cpu)
    return jax.tree.map(one, tree)


def serve_phase(cfg, seed, *, prompt_len=PROMPT_LEN, max_context=MAX_CONTEXT,
                new_tokens=NEW_TOKENS, requests=REQUESTS, slots=SLOTS):
    """Serve ``requests`` prompts through the continuous batched engine on
    the paged store; check budgets and one verify step against the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import ServeConfig, SSVConfig
    from repro.core import draft as draft_lib
    from repro.core import engine as engine_lib
    from repro.core import schedule as schedule_lib
    from repro.core.tree import build_topology
    from repro.models import model

    dcfg = draft_lib.draft_config(cfg)
    key = jax.random.PRNGKey(seed)
    tp = model.init(key, cfg)
    dp = model.init(jax.random.fold_in(key, 1), dcfg)
    ssv = SSVConfig()
    serve_cfg = ServeConfig(max_new_tokens=new_tokens, max_context=max_context,
                            ssv=ssv, use_planner=False, kv_backend="paged")
    prompts = [np.random.default_rng((seed, i)).integers(
        0, cfg.vocab_size, prompt_len) for i in range(requests)]
    reqs = [schedule_lib.Request(req_id=i, prompt=p)
            for i, p in enumerate(prompts)]

    eng = engine_lib.BatchedSSVEngine(tp, cfg, dp, dcfg, serve_cfg)
    res = eng.serve_continuous(reqs, num_slots=slots,
                               max_new_tokens=new_tokens)
    print(f"[serve] {res.total_tokens} tokens in {res.steps} fused steps, "
          f"occupancy {res.mean_occupancy:.2f}, peak page occupancy "
          f"{res.peak_page_occupancy:.2f}, kv bytes {res.kv_bytes}, "
          f"kernel/step caches {res.kernel_cache}", flush=True)
    for r, gen in zip(res.requests, res.results):
        toks = np.asarray(gen.tokens)
        if len(toks) != new_tokens:
            raise AssertionError(f"request {r.req_id} finished with "
                                 f"{len(toks)} tokens, budget {new_tokens}")
        if toks.min() < 0 or toks.max() >= cfg.vocab_size:
            raise AssertionError(f"request {r.req_id}: token out of vocab")

    # one tree-verify step of the target, chip (bf16) against host (float32)
    topo = build_topology(ssv.tree_depth, ssv.tree_width, ssv.traversal,
                          ssv.tree_budget)
    T = topo.num_nodes
    _, caches = engine_lib.jit_prefill(cfg, max_context)(
        tp, jnp.asarray(prompts[0][:-1], jnp.int32)[None])
    draft = np.random.default_rng((seed, requests)).integers(
        0, cfg.vocab_size, (1, T)).astype(np.int32)
    draft[0, 0] = prompts[0][-1]
    positions = (prompt_len - 1 + topo.depths)[None].astype(np.int32)
    tmask = topo.mask[None]
    parents = topo.parents.astype(np.int32)

    def verify(c):
        return jax.jit(lambda p, ca, *a: model.verify_step(p, c, ca, *a,
                                                           ssv)[0])
    args = (draft, positions, tmask, parents)
    chip = np.asarray(verify(cfg)(tp, caches, *args), np.float32)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    host = np.asarray(verify(cfg32)(to_host_f32(tp), to_host_f32(caches),
                                    *to_host_f32(args)))
    if chip.shape != (1, T, cfg.vocab_size) or not np.isfinite(chip).all():
        raise AssertionError(f"chip logits: shape {chip.shape}, finite "
                             f"{bool(np.isfinite(chip).all())}")
    err = rel_l2(chip, host)
    top1 = float((chip.argmax(-1) == host.argmax(-1)).mean())
    print(f"[serve] verify-step logits vs host float32: rel L2 {err:.3e} "
          f"(tol {LOGITS_REL_L2_TOL:.0e}), top-1 agreement {top1:.3f} over "
          f"{T} nodes", flush=True)
    if not err <= LOGITS_REL_L2_TOL:
        raise AssertionError(f"verify-step logits rel L2 {err:.3e} > "
                             f"{LOGITS_REL_L2_TOL:.0e}")

    # reported only: greedy agreement with plain autoregressive decoding
    agree = []
    for p, gen in zip(prompts, res.results):
        ar = engine_lib.autoregressive_decode(tp, cfg, p, new_tokens,
                                              max_context).tokens
        agree.append(float(np.mean(np.asarray(gen.tokens) == ar)))
    print(f"[serve] greedy agreement with autoregressive_decode per "
          f"request: {[round(a, 3) for a in agree]} (reported, not gated)",
          flush=True)
    return tp


def kernel_phase(cfg, seed, *, context=KERNEL_CONTEXT):
    """The kernel-backed NSA verify layer against its jnp reference, both on
    the default device, for refresh and reuse layers over dense and paged
    KV."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import SSVConfig
    from repro.core import kvstore
    from repro.core.tree import build_topology
    from repro.kernels.nsa_verify import ops
    from repro.models import nsa as nsa_lib

    nsa = cfg.nsa
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = nsa_lib.nsa_init(keys[0], cfg, dtype)
    shape = (1, context, cfg.num_kv_heads, cfg.head_dim)
    k = jax.random.normal(keys[1], shape, dtype)
    v = jax.random.normal(keys[2], shape, dtype)
    k_cmp, v_cmp = nsa_lib.compress_kv(params, k, v, nsa)
    cmp = {"k_cmp": k_cmp, "v_cmp": v_cmp}
    ssv = SSVConfig()
    topo = build_topology(ssv.tree_depth, ssv.tree_width)
    T = topo.num_nodes
    prefix = context - 2 * nsa.sel_block - 7       # ragged last block
    x = jax.random.normal(keys[3], (1, T, cfg.d_model), dtype)
    positions = jnp.asarray(prefix + topo.depths, jnp.int32)[None]
    tmask = jnp.asarray(topo.mask)[None]
    # paged copy of the same cache: logical page i lives at physical page
    # (i * 7) mod P in a pool with spare pages
    ps = nsa.sel_block
    mp = context // ps
    P = mp + 8
    perm = (np.arange(mp) * 7) % P
    pool = lambda a: jnp.zeros((P, ps) + shape[2:], dtype).at[perm].set(
        a[0].reshape((mp, ps) + shape[2:]))
    paged = kvstore.KVView(pool(k), pool(v), jnp.asarray(perm, jnp.int32)[None])

    ref = jax.jit(functools.partial(nsa_lib.nsa_verify_ref, cfg=cfg))
    layer = jax.jit(functools.partial(ops.nsa_verify_kernel_layer, cfg=cfg,
                                      C=ssv.group_size, mode=ssv.group_mode),
                    static_argnames=("reuse",))
    common = dict(params=params, x=x, cmp_cache=cmp, prefix_len=prefix,
                  positions=positions, tree_mask=tmask)
    worst = 0.0
    for store, cache in (("dense", {"k": k, "v": v}), ("paged", paged)):
        out_r, _, (si, sv) = ref(cache=cache, **common)
        out_k, _, (si_k, _) = layer(cache=cache, reuse=False, **common)
        out_u, _, _ = layer(cache=cache, reuse=True, sel_idx=si,
                            sel_valid=sv, **common)
        out_ru = ref(cache=cache, sel_idx=si, sel_valid=sv, **common)[0]
        for name, got, want in (("refresh", out_k, out_r),
                                ("reuse", out_u, out_ru)):
            got = np.asarray(got, np.float32)
            if not np.isfinite(got).all():
                raise AssertionError(f"{store} {name}: non-finite output")
            err = rel_max(got, want)
            worst = max(worst, err)
            print(f"[kernel] {store} {name}: max|kernel-ref|/max|ref| "
                  f"{err:.3e} (tol {KERNEL_REL_MAX_TOL:.0e}), "
                  f"selection equal {bool((si == si_k).all())}", flush=True)
    if not worst <= KERNEL_REL_MAX_TOL:
        raise AssertionError(f"kernel layer error {worst:.3e} > "
                             f"{KERNEL_REL_MAX_TOL:.0e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and KV")
    args = ap.parse_args(argv)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no accelerator: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this check runs only on a TPU and never falls back to the CPU",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro import configs
        from repro import obs  # noqa: F401  (listens for compiles from here on)
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    cfg = configs.get_config("ssv-nsa-1b")

    ok_serve, _ = timed_phase("serve", lambda: serve_phase(cfg, args.seed))
    ok_kernel, _ = timed_phase("kernel", lambda: kernel_phase(cfg, args.seed))
    tl, bc, cl, req, hit = compile_totals()
    print(f"total: trace+lower {tl:.2f}s, backend compile {bc:.2f}s, cache "
          f"loads {cl:.2f}s; persistent cache {hit} hits / {req - hit} "
          "misses", flush=True)
    if not (ok_serve and ok_kernel):
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
