"""Serving driver CLI: SSV speculative serving of an architecture.

  PYTHONPATH=src python -m repro.launch.serve --arch ssv-nsa-1b --reduced \
      --tokens 64 --precision-class Approx+Reuse
  PYTHONPATH=src python -m repro.launch.serve --reduced --continuous \
      --batch 4 --bucketed --profile-json profile.json --warmup

Loads (or randomly initializes) target + draft, builds a small offline
profile if planning is requested, and serves a batch of synthetic prompts,
reporting accepted-token throughput vs the autoregressive baseline.
``--bucketed`` serves a mixed-length workload through bucket-local
execution groups (one fused step per context-regime bucket, each under the
profile's strategy for that bucket — the profile JSON is a
``planner_lib.Profile`` from ``Profile.to_json``); ``--warmup``
AOT-compiles every reachable (strategy, group size) step before serving.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs as cfglib
from repro.config import ServeConfig, SSVConfig
from repro.core import draft as draft_lib
from repro.core import engine as engine_lib
from repro.core import planner as planner_lib
from repro.core import schedule as schedule_lib
from repro.data.synthetic import SyntheticConfig, SyntheticCorpus
from repro.models import model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ssv-nsa-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--batch", type=int, default=1,
                    help=">1 serves all prompts through the vectorized "
                         "BatchedSSVEngine in one fused step per iteration")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over --batch slots: admit "
                         "prompts into freed slots mid-flight (Poisson "
                         "arrival replay via --arrival-rate) instead of "
                         "serving drain-then-refill groups")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="continuous-mode Poisson arrivals per fused step "
                         "(<=0: all requests arrive at t=0)")
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--kv-backend", default="dense", choices=("dense", "paged"),
                    help="KV-cache store (core/kvstore.py): dense keeps "
                         "per-slot max_context buffers; paged shares a "
                         "physical page pool across requests via per-row "
                         "page tables, so serving memory scales with live "
                         "tokens — pair with --kv-num-pages to cap the pool")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="tokens per KV page (0 = the model's nsa.sel_block, "
                         "which makes selected-block gather a page-table "
                         "lookup; must be a sel_block multiple)")
    ap.add_argument("--kv-num-pages", type=int, default=0,
                    help="physical pages in the shared pool (0 = worst-case "
                         "slots*max_context/page_size — no memory win; size "
                         "it to expected live tokens and the scheduler "
                         "admits on free-page headroom)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--precision-class", default="Strict",
                    choices=list(planner_lib.PRECISION_CLASSES))
    ap.add_argument("--tree-depth", type=int, default=4)
    ap.add_argument("--tree-width", type=int, default=2)
    ap.add_argument("--bucketed", action="store_true",
                    help="continuous mode: partition the batch into context-"
                         "regime execution groups, each stepping under its "
                         "bucket's profile strategy (needs --profile-json); "
                         "serves a mixed-length prompt workload")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every reachable (strategy, group size) "
                         "fused step before serving (bucketed only)")
    ap.add_argument("--profile-json", default=None,
                    help="offline profile (planner_lib.Profile JSON, e.g. "
                         "written via Profile.to_json) backing --bucketed")
    ap.add_argument("--baseline", action="store_true",
                    help="also run the autoregressive decode baseline")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.bucketed:
        if not args.continuous:
            raise ValueError("--bucketed groups the continuous batch; "
                             "add --continuous")
        if not args.profile_json:
            raise ValueError(
                "--bucketed needs an offline profile to rank strategies per "
                "context bucket: pass --profile-json <path> (a "
                "planner_lib.Profile serialized with Profile.to_json)")
    if args.warmup and not args.bucketed:
        raise ValueError("--warmup pre-compiles the bucketed group-step "
                         "cache; add --bucketed")

    cfg = cfglib.reduced(args.arch) if args.reduced else cfglib.get_config(args.arch)
    if cfg.attention != "nsa":
        cfg = cfglib.nsa_variant(cfg) if cfg.d_ff or cfg.block_pattern == ("attn",) else cfg
    dcfg = draft_lib.draft_config(cfg)
    key = jax.random.PRNGKey(args.seed)
    tp = model.init(key, cfg)
    dp = model.init(jax.random.fold_in(key, 1), dcfg)

    mode, reuse = planner_lib.class_constraints(args.precision_class)
    sched = planner_lib.default_schedule(cfg.num_layers) if reuse else ()
    ssv = SSVConfig(tree_depth=args.tree_depth, tree_width=args.tree_width,
                    group_size=4 if mode == "approx" else 2, group_mode=mode,
                    refresh_schedule=sched,
                    precision_class=args.precision_class)
    serve_cfg = ServeConfig(max_new_tokens=args.tokens,
                            temperature=args.temperature,
                            max_context=min(cfg.max_seq_len, 2048), ssv=ssv,
                            use_planner=False,
                            kv_backend=args.kv_backend,
                            kv_page_size=args.kv_page_size,
                            kv_num_pages=args.kv_num_pages)

    corpus = SyntheticCorpus(SyntheticConfig(vocab_size=cfg.vocab_size))
    if args.bucketed:
        # mixed-length workload: spread prompt lengths across the profile's
        # context buckets so the planner actually forms several groups
        lens = [max(8, args.prompt_len // 2), args.prompt_len,
                args.prompt_len * 2]
        prompts = [corpus.batch(i, 1, lens[i % len(lens)])[0]
                   for i in range(args.prompts)]
    else:
        prompts = [corpus.batch(i, 1, args.prompt_len)[0]
                   for i in range(args.prompts)]

    if args.continuous:     # any batch size: --batch is the slot count
        planner = None
        if args.bucketed:
            with open(args.profile_json) as f:
                profile = planner_lib.Profile.from_json(f.read())
            planner = planner_lib.BatchPlanner(profile, args.precision_class)
        eng = engine_lib.BatchedSSVEngine(tp, cfg, dp, dcfg, serve_cfg,
                                          planner=planner)
        arrivals = schedule_lib.poisson_arrivals(
            len(prompts), args.arrival_rate, seed=args.seed)
        reqs = [schedule_lib.Request(req_id=i, prompt=p,
                                     arrival=float(arrivals[i]))
                for i, p in enumerate(prompts)]
        res = eng.serve_continuous(reqs, num_slots=args.batch,
                                   max_new_tokens=args.tokens,
                                   warmup=args.warmup)
        for req, gen in zip(res.requests, res.results):
            delay = (f"{req.queue_delay:.1f}" if req.queue_delay is not None
                     else "n/a (never admitted)")
            print(f"prompt {req.req_id}: {len(gen.tokens)} tokens, "
                  f"arrival {req.arrival:.1f}, queue delay {delay} steps")
        print(f"continuous over {args.batch} slots: {res.total_tokens} tokens "
              f"in {res.wall_s:.2f}s ({res.aggregate_throughput:.1f} tok/s "
              f"aggregate, {res.steps} fused steps, "
              f"occupancy {res.mean_occupancy:.2f}, "
              f"queue delay {res.mean_queue_delay_steps:.1f} steps)")
        if args.bucketed:
            occ = ", ".join(f"bucket{b}={v:.2f}"
                            for b, v in sorted(res.bucket_occupancy.items()))
            print(f"bucketed: {res.group_launches} group launches ({occ}); "
                  f"step cache {res.kernel_cache['step_cache_hits']} hits / "
                  f"{res.kernel_cache['step_cache_misses']} misses")
        return

    if args.batch > 1:
        eng = engine_lib.BatchedSSVEngine(tp, cfg, dp, dcfg, serve_cfg)
        for lo in range(0, len(prompts), args.batch):
            group = prompts[lo : lo + args.batch]
            batch = eng.generate_batch(group, max_new_tokens=args.tokens)
            for i, res in enumerate(batch.results):
                print(f"prompt {lo + i}: {len(res.tokens)} tokens, "
                      f"mean accepted/step {res.mean_accepted:.2f}")
            print(f"batch[{lo}:{lo + len(group)}]: {batch.total_tokens} tokens in "
                  f"{batch.wall_s:.2f}s ({batch.aggregate_throughput:.1f} tok/s "
                  f"aggregate, {batch.steps} fused steps)")
        return

    eng = engine_lib.SSVEngine(tp, cfg, dp, dcfg, serve_cfg)
    for i, prompt in enumerate(prompts):
        res = eng.generate(prompt, max_new_tokens=args.tokens)
        print(f"prompt {i}: {len(res.tokens)} tokens, "
              f"mean accepted/step {res.mean_accepted:.2f}, "
              f"throughput {res.accepted_token_throughput:.1f} tok/s")
        if args.baseline:
            bl = engine_lib.autoregressive_decode(
                tp, cfg, prompt, len(res.tokens), serve_cfg.max_context,
                temperature=args.temperature)
            print(f"  AR baseline: {bl.accepted_token_throughput:.1f} tok/s "
                  f"-> speedup {res.accepted_token_throughput / max(bl.accepted_token_throughput, 1e-9):.2f}x")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
