"""How ``correct`` is decided: the served tokens against the plain
reference.

Once the window has closed, a sample of the requests the run served, drawn
from the seed and always holding the request with the longest sequence, is
run through the configuration's reference (``bench/references/``) over its
prompt and served tokens. For every served token the gap is the
reference's best logit at that position less the reference's logit of the
served token: 0 where the served token is the reference's greedy choice.
The numbers compared are the widest gap and the mean gap over the
compared tokens; the run is correct when each number the configuration's
``check`` group names is within its limit and every sampled token was
served.

The control puts the reference itself in the program's place, computed in
float8 e4m3 wherever the configuration states bf16: every linear layer's
operands, the residual stream, K and V. Its gap is that of the token the
float8 reference puts first, read in the float32 reference's logits.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence

import numpy as np

from bench import traffic


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def sample(reqs: Sequence, k: int, seed: int) -> List:
    """``k`` served requests drawn from the seed, the longest among them."""
    served = [r for r in reqs if r.tokens]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.prompt) + len(r.tokens), -r.req_id))
    rest = [r for r in served if r is not longest]
    rng = traffic.rng_for(seed, 7)
    pick = rng.permutation(len(rest))[: max(k - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


PAD = 512


def sequence_of(req):
    """(tokens, bounds, at, served) of one request for the reference: the
    prompt and served tokens but the last, padded to ``PAD``; the sparse
    bound of every position; the position whose logits chose each served
    token; and those tokens.

    The bounds follow from the prompt's length and how many tokens each
    emission brought: every token of an emission was verified against the
    prefix committed before its step, which ends at the position of that
    step's root, the last token committed before it."""
    P, m = len(req.prompt), len(req.tokens)
    counts = np.asarray([n for _, n in req.emissions], np.int64)
    before = np.cumsum(counts) - counts
    seq = np.concatenate([np.asarray(req.prompt, np.int32),
                          np.asarray(req.tokens[:-1], np.int32)])
    S = _pad(len(seq) + 1, PAD)
    tokens = np.zeros(S, np.int32)
    tokens[: len(seq)] = seq
    bounds = np.arange(S, dtype=np.int32)
    at = P - 1 + np.arange(m, dtype=np.int32)
    bounds[at] = P - 1 + np.repeat(before, counts)
    n_pad = max(64, 1 << (m - 1).bit_length())
    at_pad = np.full(n_pad, at[-1], np.int32)
    at_pad[:m] = at
    return tokens, bounds, at_pad, np.asarray(req.tokens, np.int64)


def gaps(params, cfg: dict, req, *, control: bool = False, block_q: int = 256):
    """Per served token: (gap of the served token, gap of the control's
    first choice when ``control``) in the float32 reference's logits."""
    ref = reference_module(cfg)
    tokens, bounds, at, served = sequence_of(req)
    m = len(served)
    lg = np.asarray(ref.logits(params, cfg, tokens, bounds, at,
                               block_q=block_q))[:m].astype(np.float64)
    best = lg.max(axis=-1)
    served_gap = best - lg[np.arange(m), served]
    if not control:
        return served_gap, None
    lc = np.asarray(ref.logits(params, cfg, tokens, bounds, at, fp8=True,
                               block_q=block_q))[:m]
    ctl_gap = best - lg[np.arange(m), lc.argmax(axis=-1)]
    return served_gap, ctl_gap


def readings(gap_lists) -> dict:
    """The numbers compared, from the per-token gaps of the sample: the
    widest gap, and the mean gap over every compared token."""
    g = np.concatenate(gap_lists) if gap_lists else np.zeros(0)
    return {"logit_gap_max": float(g.max()) if len(g) else float("inf"),
            "logit_gap_mean": float(g.mean()) if len(g) else float("inf")}


def judge(reqs: Sequence, params, cfg: dict, mix: dict, seed: int,
          *, control: bool = False, block_q: int = 256) -> dict:
    """The comparison that decides ``correct``; returns the numbers
    compared with their limits (the configuration's ``check`` group names
    each number and its limit). With ``control`` (never in a benchmark
    run) the control's readings on the same tokens are added."""
    limits = {k: float(v) for k, v in cfg["check"].items()}
    picked = sample(reqs, int(mix["check"]["requests"]), seed)
    served, ctl = [], []
    for r in picked:
        g, gc = gaps(params, cfg, r, control=control, block_q=block_q)
        served.append(g)
        if control:
            ctl.append(gc)
    got = readings(served)
    n_tok = sum(len(g) for g in served)
    complete = all(len(r.tokens) == sum(n for _, n in r.emissions)
                   for r in picked)
    ok = bool(picked) and complete and all(got[k] <= v for k, v in limits.items())
    checks = {k: {"value": got[k], "limit": v} for k, v in limits.items()}
    checks["tokens_compared"] = {"value": n_tok, "limit": 1}
    checks["requests_compared"] = {"value": len(picked), "limit": 1}
    out = {"correct": ok, "checks": checks, "readings": got}
    if control:
        out["control"] = readings(ctl)
    return out
