"""Fig. 8 reproduction: end-to-end generation throughput across draft-tree
shapes (D, k), SSV variants vs the autoregressive NSA decode baseline.

Also the serving-hot-path regression harness:
  * host-transfer accounting — asserts the spec-decode loop no longer pulls
    the (T, vocab) verification logits to the host (only path tokens /
    counts / bonus cross);
  * batched-vs-sequential aggregate throughput (BatchedSSVEngine with
    batch=R vs R sequential SSVEngine.generate calls);
  * continuous-batching vs drain-then-refill serving (mid-flight slot
    admission over a queued mixed-budget workload, with slot-occupancy and
    queue-delay stats);
  * a BENCH_e2e.json snapshot next to the repo root so the perf trajectory
    is measurable PR over PR.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks import common
from repro.config import ServeConfig, SSVConfig
from repro.core import engine as engine_lib
from repro.core import planner as planner_lib
from repro.core import schedule as schedule_lib

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_e2e.json")


def _serve_cfg(ssv, tokens):
    return ServeConfig(max_new_tokens=tokens, temperature=0.0,
                       max_context=1024, ssv=ssv, use_planner=False)


def main(csv=None, grid=((2, 2), (3, 2), (4, 2), (3, 4)), tokens=48,
         quick=False, batch=4):
    csv = csv or common.Csv("e2e")
    if quick:
        grid, tokens, batch = ((2, 2), (3, 2)), 12, 2
    tp, tcfg, dp, dcfg = common.get_models(train_steps=25 if quick else 80)
    prompt = common.prompts(1, 96)[0]
    reuse_sched = tuple(range(1, tcfg.num_layers, 2))
    report = {"tokens": tokens, "grid": [list(g) for g in grid], "variants": {}}

    # autoregressive NSA decode baseline (the paper's 49 tok/s anchor)
    ar = engine_lib.autoregressive_decode(tp, tcfg, prompt, tokens, 1024)
    base_tps = ar.accepted_token_throughput
    csv.row("ar_decode_baseline", 1e6 / max(base_tps, 1e-9), f"{base_tps:.1f}tok/s")
    report["ar_decode_tok_s"] = base_tps

    for (D, k) in grid:
        for variant, sched in (("norefresh", ()), ("reuse", reuse_sched)):
            ssv = SSVConfig(tree_depth=D, tree_width=k, traversal="bfs",
                            group_size=2, group_mode="exact",
                            refresh_schedule=sched)
            eng = engine_lib.SSVEngine(tp, tcfg, dp, dcfg, _serve_cfg(ssv, tokens))
            res = eng.generate(prompt, max_new_tokens=tokens)
            tps = res.accepted_token_throughput
            # tokens-per-target-pass is the hardware-transferable gain: on
            # memory-bound accelerators step latency is ~flat in gamma
            # (paper Fig. 7), so emitted-per-pass bounds the speedup there.
            per_pass = res.mean_accepted + 1.0
            csv.row(f"D{D}_k{k}_{variant}",
                    1e6 / max(tps, 1e-9),
                    f"{tps:.1f}tok/s;speedup={tps / max(base_tps, 1e-9):.2f}x;"
                    f"acc={res.mean_accepted:.2f};tok_per_pass={per_pass:.2f}")
            report["variants"][f"D{D}_k{k}_{variant}"] = {
                "tok_s": tps, "speedup_vs_ar": tps / max(base_tps, 1e-9),
                "mean_accepted": res.mean_accepted}

    # ---- host-transfer accounting: the fused step returns a few ints, not
    # (T, vocab) logits
    ssv0 = SSVConfig(tree_depth=grid[0][0], tree_width=grid[0][1],
                     traversal="bfs", group_size=2, group_mode="exact")
    eng = engine_lib.SSVEngine(tp, tcfg, dp, dcfg, _serve_cfg(ssv0, tokens))
    res = eng.generate(prompt, max_new_tokens=tokens)
    T = ssv0.num_draft_tokens() + 1
    per_step = engine_lib.step_host_transfer_elems(ssv0)
    logits_elems = T * tcfg.vocab_size
    assert per_step < logits_elems, (
        f"spec-decode step transfers {per_step} elems/step — expected far "
        f"fewer than the {logits_elems} of a (T, vocab) logits pull")
    observed = max(s.host_elems for s in res.steps)
    assert observed < logits_elems
    csv.row("host_transfer_elems_per_step", float(per_step),
            f"vs_logits={logits_elems};ratio={per_step / logits_elems:.5f}")
    report["host_transfer"] = {"elems_per_step": per_step,
                               "logits_elems": logits_elems}

    # ---- batched vs sequential serving throughput
    prompts = common.prompts(batch, 96, start=200)
    seq_t0 = time.time()
    seq_tokens = 0
    for p in prompts:
        e = engine_lib.SSVEngine(tp, tcfg, dp, dcfg, _serve_cfg(ssv0, tokens))
        seq_tokens += len(e.generate(p, max_new_tokens=tokens).tokens)
    seq_dt = time.time() - seq_t0
    seq_tps = seq_tokens / max(seq_dt, 1e-9)

    beng = engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg, _serve_cfg(ssv0, tokens))
    beng.generate_batch(prompts, max_new_tokens=tokens)   # warm the jit
    bres = beng.generate_batch(prompts, max_new_tokens=tokens)
    bat_tps = bres.aggregate_throughput
    bat_kv = beng.kv_cache_bytes()
    csv.row(f"serve_sequential_x{batch}", 1e6 / max(seq_tps, 1e-9),
            f"{seq_tps:.1f}tok/s_aggregate")
    csv.row(f"serve_batched_x{batch}", 1e6 / max(bat_tps, 1e-9),
            f"{bat_tps:.1f}tok/s_aggregate;"
            f"speedup_vs_sequential={bat_tps / max(seq_tps, 1e-9):.2f}x;"
            f"peak_kv_bytes={bat_kv}")
    report["serving"] = {"batch": batch,
                         "sequential_tok_s": seq_tps,
                         "batched_tok_s": bat_tps,
                         "batched_speedup": bat_tps / max(seq_tps, 1e-9),
                         "peak_kv_bytes": bat_kv}

    # ---- continuous batching vs drain-then-refill
    # A realistic serving mix: 2*batch queued requests, each drain wave
    # carrying one straggler (full token budget) among short jobs.
    # Drain-then-refill holds every freed slot hostage until the wave's
    # straggler finishes; continuous batching admits the next queued request
    # into a slot the moment it frees (per-slot re-prefill mid-flight). Same
    # engine, same fused step, same per-request budgets — the only variable
    # is the slot admission policy.
    n_req = 2 * batch
    cont_prompts = common.prompts(n_req, 96, start=300)
    budgets = [tokens if i % batch == 0 else max(4, tokens // 4)
               for i in range(n_req)]

    def _reqs(lo, hi):
        return [schedule_lib.Request(req_id=i, prompt=cont_prompts[i],
                                     max_new_tokens=budgets[i], arrival=0.0)
                for i in range(lo, hi)]

    def _drain():
        tok, steps, wall = 0, 0, 0.0
        for lo in range(0, n_req, batch):
            eng = engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg,
                                              _serve_cfg(ssv0, tokens))
            r = eng.serve_continuous(_reqs(lo, min(lo + batch, n_req)),
                                     num_slots=batch)
            tok += r.total_tokens
            steps += r.steps
            wall += r.wall_s
        return tok, steps, wall

    def _continuous():
        eng = engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg,
                                          _serve_cfg(ssv0, tokens))
        return eng.serve_continuous(_reqs(0, n_req), num_slots=batch)

    _drain(); _continuous()                     # warm the jit caches
    # best-of-2: a single timed pass is noisy on shared CPU runners
    d_tok, d_steps, d_wall = min((_drain() for _ in range(2)),
                                 key=lambda r: r[2])
    cres = min((_continuous() for _ in range(2)), key=lambda r: r.wall_s)
    drain_tps = d_tok / max(d_wall, 1e-9)
    cont_tps = cres.aggregate_throughput
    csv.row(f"serve_drain_refill_x{batch}", 1e6 / max(drain_tps, 1e-9),
            f"{drain_tps:.1f}tok/s_aggregate;fused_steps={d_steps}")
    csv.row(f"serve_continuous_x{batch}", 1e6 / max(cont_tps, 1e-9),
            f"{cont_tps:.1f}tok/s_aggregate;fused_steps={cres.steps};"
            f"occupancy={cres.mean_occupancy:.2f};"
            f"speedup_vs_drain={cont_tps / max(drain_tps, 1e-9):.2f}x;"
            f"peak_kv_bytes={cres.kv_bytes}")
    report["continuous"] = {
        "batch": batch, "requests": n_req,
        "drain_tok_s": drain_tps, "continuous_tok_s": cont_tps,
        "speedup_vs_drain": cont_tps / max(drain_tps, 1e-9),
        "drain_fused_steps": d_steps, "continuous_fused_steps": cres.steps,
        "mean_occupancy": cres.mean_occupancy,
        "mean_queue_delay_steps": cres.mean_queue_delay_steps,
        "peak_kv_bytes": cres.kv_bytes}

    # ---- paged vs dense KV store (low-occupancy continuous workload)
    # Mixed-length, mixed-budget requests over max_context-sized slots: the
    # dense layout allocates slots x max_context x layers KV rows no matter
    # what's live; the paged store provisions only each request's page
    # reservation (prompt + budget + speculative headroom), so at low
    # occupancy its peak KV bytes drop with the workload. Token equality
    # between the backends is asserted here on top of the dedicated tests.
    kv_prompts = [common.prompts(1, 64 + 32 * (i % 3), start=400 + i)[0]
                  for i in range(n_req)]
    kv_budgets = [max(4, tokens // (1 + i % 3)) for i in range(n_req)]

    def _kv_reqs():
        return [schedule_lib.Request(req_id=i, prompt=kv_prompts[i],
                                     max_new_tokens=kv_budgets[i], arrival=0.0)
                for i in range(n_req)]

    def _kv_serve(backend, num_pages=0):
        return ServeConfig(max_new_tokens=tokens, temperature=0.0,
                           max_context=1024, ssv=ssv0, use_planner=False,
                           kv_backend=backend, kv_num_pages=num_pages)

    sizer = engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg, _kv_serve("paged"))
    needs = sorted(sizer.pages_for(len(p), b)
                   for p, b in zip(kv_prompts, kv_budgets))
    pool_pages = sum(needs[-batch:])          # full slot concurrency, no waits

    def _kv_run(backend):
        eng = engine_lib.BatchedSSVEngine(
            tp, tcfg, dp, dcfg,
            _kv_serve(backend, pool_pages if backend == "paged" else 0))
        eng.serve_continuous(_kv_reqs(), num_slots=batch)        # warm the jit
        res = min((eng.serve_continuous(_kv_reqs(), num_slots=batch)
                   for _ in range(2)), key=lambda r: r.wall_s)
        return eng, res

    _, kv_dense = _kv_run("dense")
    _, kv_paged = _kv_run("paged")
    for rd, rp in zip(kv_dense.results, kv_paged.results):
        assert np.array_equal(rd.tokens, rp.tokens), \
            "paged backend diverged from dense on the serving workload"
    assert kv_paged.kv_bytes < kv_dense.kv_bytes, (
        f"paged KV footprint {kv_paged.kv_bytes} not below dense "
        f"{kv_dense.kv_bytes} on the low-occupancy workload")
    ratio = kv_paged.kv_bytes / max(kv_dense.kv_bytes, 1)
    tput_ratio = kv_paged.aggregate_throughput / max(
        kv_dense.aggregate_throughput, 1e-9)
    csv.row(f"serve_kv_dense_x{batch}",
            1e6 / max(kv_dense.aggregate_throughput, 1e-9),
            f"{kv_dense.aggregate_throughput:.1f}tok/s_aggregate;"
            f"peak_kv_bytes={kv_dense.kv_bytes}")
    csv.row(f"serve_kv_paged_x{batch}",
            1e6 / max(kv_paged.aggregate_throughput, 1e-9),
            f"{kv_paged.aggregate_throughput:.1f}tok/s_aggregate;"
            f"peak_kv_bytes={kv_paged.kv_bytes};bytes_vs_dense={ratio:.2f};"
            f"tput_vs_dense={tput_ratio:.2f};"
            f"page_occ={kv_paged.mean_page_occupancy:.2f}")
    report["kv_store"] = {
        "batch": batch, "requests": n_req, "pool_pages": pool_pages,
        # fraction of the dense layout's token capacity the workload can
        # ever occupy — the low-occupancy regime where paging pays
        "dense_capacity_utilization":
            pool_pages * sizer._page_size / (batch * 1024),
        "dense_tok_s": kv_dense.aggregate_throughput,
        "paged_tok_s": kv_paged.aggregate_throughput,
        "throughput_ratio": tput_ratio,
        "dense_peak_kv_bytes": kv_dense.kv_bytes,
        "paged_peak_kv_bytes": kv_paged.kv_bytes,
        "kv_bytes_ratio": ratio,
        "mean_occupancy": kv_paged.mean_occupancy,
        "mean_page_occupancy": kv_paged.mean_page_occupancy,
        "peak_page_occupancy": kv_paged.peak_page_occupancy,
        "token_equal": True}

    # ---- bucket-local vs shared-strategy mixed-length serving
    # The paper's third pillar at batch scale: a mixed-length continuous
    # batch under ONE shared strategy runs its short-context rows on the
    # long-context tree topology (today's planner picks by max context), so
    # every short-row step verifies a deep tree it cannot fill. Bucket-local
    # execution groups give each context regime its profile strategy —
    # short rows step a shallow tree, long rows keep the deep one — with
    # per-request token streams byte-identical to single-stream generation
    # under the row's bucket strategy (asserted below).
    buckets = ((0, 64), (64, 4096))
    short_strat = SSVConfig(tree_depth=1, tree_width=2, traversal="bfs",
                            group_size=2, group_mode="exact")
    long_strat = SSVConfig(tree_depth=4, tree_width=2, traversal="bfs",
                           group_size=2, group_mode="exact")
    # expected_accept 0.0: the runtime guard never refines, so strategies —
    # and therefore tokens — are deterministic for the equality check
    profile = planner_lib.Profile(
        table={(0, "Strict"): [planner_lib.ProfileEntry(short_strat, 0.0, 1.0)],
               (1, "Strict"): [planner_lib.ProfileEntry(long_strat, 0.0, 1.0)]},
        buckets=buckets)
    n_short = 2 * batch
    n_long = max(1, batch // 2)
    mixed = ([common.prompts(1, 24, start=500 + i)[0] for i in range(n_short)]
             + [common.prompts(1, 96, start=600 + i)[0] for i in range(n_long)])
    mixed_budgets = ([max(4, tokens // 4)] * n_short + [tokens] * n_long)

    def _mixed_reqs():
        return [schedule_lib.Request(req_id=i, prompt=mixed[i],
                                     max_new_tokens=mixed_budgets[i],
                                     arrival=0.0)
                for i in range(len(mixed))]

    # per-request ground truth: single-stream generation under the strategy
    # the profile assigns to that request's bucket
    bucket_refs = []
    for p, b in zip(mixed, mixed_budgets):
        strat = (short_strat if planner_lib.bucket_of(len(p), buckets) == 0
                 else long_strat)
        e = engine_lib.SSVEngine(tp, tcfg, dp, dcfg, _serve_cfg(strat, tokens))
        bucket_refs.append(e.generate(p, max_new_tokens=b).tokens)

    def _shared():
        # the shared-strategy baseline: what today's batch planner runs —
        # one strategy keyed on the batch's max context, i.e. the deep tree
        eng = engine_lib.BatchedSSVEngine(tp, tcfg, dp, dcfg,
                                          _serve_cfg(long_strat, tokens))
        return eng.serve_continuous(_mixed_reqs(), num_slots=batch)

    def _bucketed():
        eng = engine_lib.BatchedSSVEngine(
            tp, tcfg, dp, dcfg, _serve_cfg(long_strat, tokens),
            planner=planner_lib.BatchPlanner(profile, "Strict"))
        return eng, eng.serve_continuous(_mixed_reqs(), num_slots=batch,
                                         warmup=True)
    _shared(); beng, _ = _bucketed()            # warm the jit / AOT caches
    sres = min((_shared() for _ in range(2)), key=lambda r: r.wall_s)
    bres = min((beng.serve_continuous(_mixed_reqs(), num_slots=batch)
                for _ in range(2)), key=lambda r: r.wall_s)
    for ref, gen in zip(bucket_refs, bres.results):
        assert np.array_equal(ref, gen.tokens), (
            "bucketed serving diverged from single-stream generation under "
            "the row's bucket strategy")
    shared_tps = sres.aggregate_throughput
    buck_tps = bres.aggregate_throughput
    csv.row(f"serve_shared_strategy_x{batch}", 1e6 / max(shared_tps, 1e-9),
            f"{shared_tps:.1f}tok/s_aggregate;fused_steps={sres.steps}")
    csv.row(f"serve_bucketed_x{batch}", 1e6 / max(buck_tps, 1e-9),
            f"{buck_tps:.1f}tok/s_aggregate;"
            f"speedup_vs_shared={buck_tps / max(shared_tps, 1e-9):.2f}x;"
            f"group_launches={bres.group_launches};"
            f"step_cache_misses={bres.kernel_cache['step_cache_misses']}")
    report["bucketed"] = {
        "slots": batch, "requests": len(mixed),
        "n_short": n_short, "n_long": n_long,
        "shared_tok_s": shared_tps, "bucketed_tok_s": buck_tps,
        "speedup_vs_shared": buck_tps / max(shared_tps, 1e-9),
        "shared_fused_steps": sres.steps, "bucketed_fused_steps": bres.steps,
        "group_launches": bres.group_launches,
        "bucket_occupancy": {str(k): v
                             for k, v in bres.bucket_occupancy.items()},
        "step_cache": {k: v for k, v in bres.kernel_cache.items()
                       if k.startswith("step_cache")},
        "token_equal": True}

    # quick mode goes to /tmp: the committed baseline only tracks full runs
    path = "/tmp/BENCH_e2e.quick.json" if quick else BENCH_JSON
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    csv.row("bench_json", 0.0, os.path.abspath(path))
    return csv


if __name__ == "__main__":
    main()
