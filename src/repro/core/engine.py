"""SSVEngine — the end-to-end draft → sparse-verify → accept serving loop
(paper Fig. 3), with pluggable verification strategy (θ_d, θ_s), precision
class P, and planner-driven prompt adaptation.

Per generation step:
  1. the planner supplies the active strategy (tree shape, traversal,
     grouping, refresh/reuse schedule);
  2. the draft model expands a rooted token tree under the pending token;
  3. the target verifies all nodes in one tree-masked pass — NSA layers run
     the refresh/reuse schedule and exact/approx grouped selection;
  4. accept/reject picks the longest valid path + a bonus token **on
     device**, fused into the same jitted step as verification and the
     target-cache commit — the (T, vocab) verification logits never leave
     the accelerator; only the accepted path tokens, n_accepted, and the
     bonus token (a few ints) cross to the host;
  5. both models commit the accepted path's K/V (or recurrent states) with
     **donated** cache buffers — commits update the max_context-sized caches
     in place instead of double-allocating them;
  6. step statistics (A_t, T_t) feed the planner's runtime guard.

The committed sequence length is tracked host-side (updated from the
n_accepted scalar the loop fetches anyway), so the generate loop never
blocks on a device sync of ``caches["length"]``.

All device computations are jitted and cached per (config, strategy, tree
topology) — fixed shapes, no recompilation inside a generation.
`BatchedSSVEngine` vectorizes the whole step (draft expansion, tree
verification, accept, donated commits) over a request batch with
per-request lengths and completion masks.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig, ServeConfig, SSVConfig
from repro.core import accept as accept_lib
from repro.core import draft as draft_lib
from repro.core import kvstore
from repro.core import overlap as overlap_lib
from repro.core import schedule as schedule_lib
from repro.core.tree import build_topology, children_matrix
from repro.models import model


# ------------------------------------------------------------ jit caches
# ModelConfig / SSVConfig are frozen dataclasses — they hash and compare by
# value, so two equal configs share one cache entry and planner strategy
# switches never silently recompile inside a generation (each distinct
# (config, strategy, topology shape) is traced at most once; see
# tests/test_engine_batched.py::test_jit_cache_keys_by_value).
@functools.lru_cache(maxsize=64)
def jit_verify(cfg: ModelConfig, ssv: Optional[SSVConfig]):
    def ssv_verify(params, caches, tokens, positions, tmask, parents):
        return model.verify_step(params, cfg, caches, tokens, positions, tmask,
                                 parents, ssv)
    return jax.jit(ssv_verify)


@functools.lru_cache(maxsize=64)
def jit_commit(cfg: ModelConfig):
    # caches donated: the commit's output KV buffers alias the inputs —
    # no second max_context-sized allocation per step.
    def ssv_commit(params, caches, updates, accepted, n_accepted):
        return model.commit(params, cfg, caches, updates, accepted, n_accepted)
    return jax.jit(ssv_commit, donate_argnums=(1,))


@functools.lru_cache(maxsize=64)
def jit_prefill(cfg: ModelConfig, max_len: int):
    # prefill builds the caches from scratch — there is no input cache buffer
    # to donate; the prompt token array is tiny, so nothing else is worth it.
    def ssv_prefill(params, tokens):
        return model.prefill(params, cfg, tokens, max_len)
    return jax.jit(ssv_prefill)


@functools.lru_cache(maxsize=64)
def jit_decode(cfg: ModelConfig):
    def ssv_decode(params, caches, tokens):
        return model.decode_step(params, cfg, caches, tokens)
    return jax.jit(ssv_decode)


@functools.lru_cache(maxsize=64)
def jit_verify_accept(cfg: ModelConfig, ssv: SSVConfig, greedy: bool,
                      temperature: float):
    """Fused verify → tree-accept → commit step for the target model.

    The tree topology is a pure function of ``ssv`` and is closed over as
    static arrays. Only the accepted tokens / path / counts are returned to
    the caller alongside the (donated, updated-in-place) caches — the
    (T, vocab) logits tensor stays on device.

    Greedy signature:     (params, caches, tokens)
    Stochastic signature: (params, caches, tokens, node_q, accept_u, bonus_u)
    Returns (new_caches, path (pad,), tokens (pad+1,), bonus, n_accepted_path)
    where n_accepted_path counts accepted DRAFT nodes (excl. root/bonus) and
    path/n include the pending root as commit expects.
    """
    topo = build_topology(ssv.tree_depth, ssv.tree_width, ssv.traversal,
                          ssv.tree_budget)
    depths = jnp.asarray(topo.depths)
    tmask = jnp.asarray(topo.mask)
    parents = jnp.asarray(topo.parents)
    child_mat = jnp.asarray(children_matrix(topo))
    maxd = int(topo.depths.max()) if topo.num_nodes else 0

    def core(params, caches, tokens, accept_fn):
        B, T = tokens.shape
        positions = (depths[None] + caches["length"]).astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (B, T))
        logits, updates = model.verify_step(
            params, cfg, caches, tokens, positions,
            jnp.broadcast_to(tmask[None], (B, T, T)), parents, ssv)
        path, out_tokens, bonus, n_acc = accept_fn(tokens[0], logits[0])
        new_caches = model.commit(params, cfg, caches, updates,
                                  path[None], (n_acc + 1)[None])
        return new_caches, path, out_tokens, bonus, n_acc

    if greedy:
        def ssv_verify_accept(params, caches, tokens):
            return core(params, caches, tokens,
                        lambda tk, lg: accept_lib.greedy_tree_accept_device(
                            child_mat, maxd, tk, lg))
    else:
        def ssv_verify_accept(params, caches, tokens, node_q, accept_u, bonus_u):
            return core(params, caches, tokens,
                        lambda tk, lg: accept_lib.stochastic_tree_accept_device(
                            child_mat, maxd, tk, lg, node_q[0], accept_u,
                            bonus_u, temperature))
    return jax.jit(ssv_verify_accept, donate_argnums=(1,))


def _resolve_store(serve_cfg: ServeConfig, target_cfg: ModelConfig) -> kvstore.KVStoreConfig:
    """Pin the page size against the TARGET model once: target and draft
    share one page table, so both pools must tile tokens identically (the
    dense-attention draft has no sel_block constraint of its own)."""
    store = kvstore.KVStoreConfig(serve_cfg.kv_backend, serve_cfg.kv_page_size,
                                  serve_cfg.kv_num_pages)
    if store.is_paged:
        store = dataclasses.replace(
            store, page_size=store.resolved_page_size(target_cfg))
    return store


def max_draft_gamma(serve_cfg: ServeConfig, planner) -> int:
    """Largest draft-tree size any step can run: the base strategy plus —
    when a planner is attached — every strategy in its profile (a mid-run
    refinement can switch to any of them)."""
    g = serve_cfg.ssv.num_draft_tokens()
    profile = getattr(planner, "profile", None)
    if profile is not None:
        for entries in profile.table.values():
            for e in entries:
                g = max(g, e.strategy.num_draft_tokens())
    return g


def step_headroom(serve_cfg: ServeConfig, planner) -> int:
    """Tokens a request's cache region must leave free beyond its budget: a
    commit writes the whole padded accepted path before the budget check
    truncates it. Both engines size admission (dense max_context bound AND
    paged page reservation) with this one bound."""
    return 2 * (max_draft_gamma(serve_cfg, planner) + 2)


def request_pages(serve_cfg: ServeConfig, planner, page_size: int,
                  max_pages: int, prompt_len: int,
                  max_new_tokens: int = 0) -> int:
    """Pages a request reserves for its whole life: committed prompt + token
    budget + speculative-step overshoot (a commit writes the padded path
    before the budget check truncates it), capped at the logical row
    capacity. ONE function sizes both the single-stream and the batched
    engines' reservations — page needs never grow mid-flight, so a full
    pool can only delay admission, never deadlock or preempt a live row."""
    budget = max_new_tokens or serve_cfg.max_new_tokens
    toks = min(prompt_len - 1 + budget + step_headroom(serve_cfg, planner),
               serve_cfg.max_context)
    return min(kvstore.pages_needed(toks, page_size), max_pages)


def kernel_cache_stats() -> Dict[str, int]:
    """Process-wide kernel-layer cache counters, reported in engine metrics
    next to ``kv_cache_bytes``: the fused-verify kernel build cache
    (``kernels/nsa_verify/ops._cached_call``) and the (T, C) query-group
    layout cache (``overlap.group_queries``). Both caches are shared by
    every engine in the process and count their lookups and builds into
    ``obs.counters()``; the ``*_cached`` entries are the caches' sizes."""
    from repro.kernels.nsa_verify import ops as nsa_ops
    c = obs.counters()
    out = {}
    for key, size in (("verify_call", nsa_ops.verify_call_cache_info().currsize),
                      ("group_layout", overlap_lib.group_queries.cache_info().currsize)):
        misses = c.get(f"kernel.{key}.builds", 0)
        out[f"{key}_hits"] = c.get(f"kernel.{key}.lookups", 0) - misses
        out[f"{key}_misses"] = misses
        out[f"{key}_cached"] = size
    return out


def step_host_transfer_elems(ssv: SSVConfig) -> int:
    """Elements the fused step hands to the host per iteration: the padded
    accepted-token vector plus the (bonus, n_accepted) scalars. Compare with
    the T × vocab logits tensor the host-side accept used to pull."""
    topo = build_topology(ssv.tree_depth, ssv.tree_width, ssv.traversal,
                          ssv.tree_budget)
    maxd = int(topo.depths.max()) if topo.num_nodes else 0
    return (maxd + 1) + 2


@dataclasses.dataclass
class StepStats:
    accepted: int          # draft tokens accepted (A_t excludes the bonus)
    emitted: int           # new tokens emitted this step (accepted + 1 bonus)
    latency_s: float       # T_t
    gamma: int             # draft tokens verified
    strategy: SSVConfig
    host_elems: int = 0    # device->host elements fetched this step


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray
    steps: List[StepStats]

    @property
    def accepted_token_throughput(self) -> float:
        tot_t = sum(s.latency_s for s in self.steps)
        tot_e = sum(s.emitted for s in self.steps)
        return tot_e / tot_t if tot_t > 0 else 0.0

    @property
    def mean_accepted(self) -> float:
        return float(np.mean([s.accepted for s in self.steps])) if self.steps else 0.0


class SSVEngine:
    """Single-sequence (B=1 per stream) speculative serving engine."""

    def __init__(self, target_params, target_cfg: ModelConfig, draft_params,
                 draft_cfg: ModelConfig, serve_cfg: ServeConfig, planner=None,
                 rng_seed: int = 0):
        if getattr(planner, "is_batch_planner", False):
            raise ValueError(
                "BatchPlanner plans bucket-local execution groups over a "
                "batch; the single-stream SSVEngine takes a RuntimePlanner — "
                "use BatchedSSVEngine for bucketed serving")
        self.tp, self.tcfg = target_params, target_cfg
        self.dp, self.dcfg = draft_params, draft_cfg
        self.serve = serve_cfg
        self.planner = planner
        self.rng = np.random.default_rng(rng_seed)
        self.t_caches = None
        self.d_caches = None
        self.pending: Optional[int] = None
        self.prompt_len = 0
        self.committed_len = 0   # host-side mirror of caches["length"]
        self.store = _resolve_store(serve_cfg, target_cfg)
        self.allocator: Optional[kvstore.PageAllocator] = None
        if self.store.is_paged:
            self._page_size = self.store.page_size
            self._max_pages = self.store.logical_pages(serve_cfg.max_context,
                                                       self._page_size)

    # -------------------------------------------------------------- setup
    def start(self, prompt_tokens: np.ndarray, max_new_tokens: int = 0):
        """prompt_tokens: (S,) — prefill both models; the last prompt token
        becomes the pending root of the first tree. Under the paged store the
        prefilled KV is re-homed into freshly allocated pages sized for
        prompt + ``max_new_tokens`` (default: the serve config budget) +
        speculative headroom."""
        toks = jnp.asarray(prompt_tokens, jnp.int32)[None]
        max_len = self.serve.max_context
        # prefill everything except the last token — it becomes the pending root
        _, self.t_caches = jit_prefill(self.tcfg, max_len)(self.tp, toks[:, :-1])
        _, self.d_caches = jit_prefill(self.dcfg, max_len)(self.dp, toks[:, :-1])
        if self.store.is_paged:
            need = request_pages(self.serve, self.planner, self._page_size,
                                 self._max_pages, len(prompt_tokens),
                                 max_new_tokens)
            self.allocator = kvstore.PageAllocator(
                self.store.resolved_num_pages(1, self._max_pages))
            pg = self.allocator.alloc(need)
            if pg is None:
                raise ValueError(
                    f"kv_num_pages={self.allocator.num_pages} pages cannot "
                    f"hold this request ({need} pages needed)")
            row = np.full((self._max_pages,), -1, np.int32)
            row[:need] = pg
            rowj = jnp.asarray(row)

            def rehome(cfg, dense_caches):
                segs = model.init_caches(cfg, 1, max_len, self.store)["segments"]
                segs = kvstore.admit_row_paged(segs, dense_caches["segments"],
                                               jnp.int32(0), rowj)
                return {"segments": segs, "length": dense_caches["length"],
                        "pages": rowj[None]}

            self.t_caches = rehome(self.tcfg, self.t_caches)
            self.d_caches = rehome(self.dcfg, self.d_caches)
        self.pending = int(prompt_tokens[-1])
        self.prompt_len = len(prompt_tokens)
        self.committed_len = self.prompt_len - 1
        if self.planner is not None:
            self.planner.begin_request(context_len=self.prompt_len)

    # -------------------------------------------------------------- one step
    def step(self, strategy: Optional[SSVConfig] = None) -> Tuple[List[int], StepStats]:
        ssv = strategy or (self.planner.current() if self.planner else self.serve.ssv)
        topo = build_topology(ssv.tree_depth, ssv.tree_width, ssv.traversal,
                              ssv.tree_budget)
        greedy = self.serve.temperature == 0.0
        t0 = time.perf_counter()
        pending = jnp.asarray([self.pending], jnp.int32)

        dverify = jit_verify(self.dcfg, None)
        tokens, node_q, d_updates = draft_lib.expand_tree(
            lambda caches, tk, pos, tm, par: dverify(self.dp, caches, tk, pos, tm, par),
            self.dcfg, self.d_caches, topo, pending,
            temperature=self.serve.temperature)

        T = topo.num_nodes
        step_fn = jit_verify_accept(self.tcfg, ssv, greedy, self.serve.temperature)
        if greedy:
            self.t_caches, path, out_tokens, bonus, n_acc = step_fn(
                self.tp, self.t_caches, tokens)
        else:
            accept_u, bonus_u = accept_lib.draw_uniforms(topo, self.rng)
            self.t_caches, path, out_tokens, bonus, n_acc = step_fn(
                self.tp, self.t_caches, tokens,
                node_q, jnp.asarray(accept_u, jnp.float32),
                jnp.float32(bonus_u))

        # draft commit consumes the on-device path — no host round-trip
        self.d_caches = jit_commit(self.dcfg)(
            self.dp, self.d_caches, d_updates, path[None], (n_acc + 1)[None])
        # the ONLY device->host transfer of the step: a few ints
        n = int(n_acc)
        emitted = np.asarray(out_tokens[: n + 1])
        self.pending = int(emitted[-1])
        self.committed_len += n + 1

        dt = time.perf_counter() - t0
        stats = StepStats(accepted=n, emitted=n + 1, latency_s=dt, gamma=T - 1,
                          strategy=ssv, host_elems=emitted.size + 2)
        if self.planner is not None:
            self.planner.observe(accepted=n, latency_s=dt)
        return [int(t) for t in emitted], stats

    # -------------------------------------------------------------- generate
    def generate(self, prompt_tokens: np.ndarray, max_new_tokens: int = 0,
                 eos_id: int = -1) -> GenerationResult:
        max_new = max_new_tokens or self.serve.max_new_tokens
        self.start(np.asarray(prompt_tokens), max_new_tokens=max_new)
        out: List[int] = []
        steps: List[StepStats] = []
        while len(out) < max_new:
            new_toks, st = self.step()
            steps.append(st)
            for t in new_toks:
                out.append(int(t))
                if t == eos_id or len(out) >= max_new:
                    break
            if out and out[-1] == eos_id:
                break
            # host-tracked committed length — no device sync in the loop
            if self.committed_len + 2 * (st.gamma + 2) >= self.serve.max_context:
                break
        return GenerationResult(tokens=np.asarray(out), steps=steps)

    def kv_cache_bytes(self) -> int:
        """Raw-KV footprint of the live caches (both models)."""
        total = 0
        for caches in (self.t_caches, self.d_caches):
            if caches is not None:
                total += kvstore.kv_cache_bytes(caches["segments"])
        return total

    def kernel_cache_stats(self) -> Dict[str, int]:
        """Kernel-layer cache hit/miss counters (process-wide)."""
        return kernel_cache_stats()


# ------------------------------------------------------------ batched engine
@dataclasses.dataclass
class BatchGenerationResult:
    """Per-request outputs plus aggregate throughput of a batched generate."""
    results: List[GenerationResult]
    steps: int
    wall_s: float

    @property
    def total_tokens(self) -> int:
        return int(sum(len(r.tokens) for r in self.results))

    @property
    def aggregate_throughput(self) -> float:
        return self.total_tokens / self.wall_s if self.wall_s > 0 else 0.0


@functools.lru_cache(maxsize=32)
def jit_batched_step(tcfg: ModelConfig, dcfg: ModelConfig, ssv: SSVConfig,
                     greedy: bool, temperature: float,
                     store: kvstore.KVStoreConfig = kvstore.DENSE):
    """One fully fused, batch-vectorized SSV step.

    The entire draft-expand → tree-verify → accept → commit chain is traced
    once for a single request row (per-row scalar length, exactly the
    single-stream semantics) and vmapped over the request batch, then jitted
    with both models' cache pytrees donated. Per-row lengths diverge freely;
    an ``active`` flag turns finished rows into no-op commits.

    Continuous batching rides on per-row ADMISSION masks: a row with
    ``admit_mask`` set had a fresh KV prefix written into its cache row by
    the per-slot re-prefill (see ``admit_row_segments``), and this launch
    resets its device length and pending root from ``admit_len`` /
    ``admit_pending`` before stepping — so one launch serves a mix of
    freshly-admitted and mid-generation rows without touching other rows.

    Greedy signature:     (tp, dp, t_segs, t_len, d_segs, d_len, pending,
                           active, admit_mask, admit_len, admit_pending)
    Stochastic signature: (..., admit_pending, accept_u (R,rounds,kmax),
                           bonus_u (R,))
      -> (t_segs', t_len', d_segs', d_len', tokens (R, pad+1), n_acc (R,))
    where segs are the caches' "segments" pytrees with leaf batch axis 1.

    Paged store: the signature gains ``pages`` (R, max_pages) after
    ``d_len``. Raw-KV leaves of both segs are the models' shared page pools
    (no batch axis — every row reads them through its page-table row inside
    the vmap), so the per-row trace runs ``commit_paged_prepare`` only and
    the pool scatters are issued once at batch level, where rows cannot
    alias (the allocator never double-assigns a page).

    The phases carry named scopes (``ssv.admit_reset``, ``ssv.draft``,
    ``ssv.verify``, ``ssv.accept``, ``ssv.commit``) in every op's metadata,
    so a profiler trace puts each device op under its phase.
    """
    topo = build_topology(ssv.tree_depth, ssv.tree_width, ssv.traversal,
                          ssv.tree_budget)
    depths = jnp.asarray(topo.depths)
    tmask = jnp.asarray(topo.mask)
    parents = jnp.asarray(topo.parents)
    child_mat = jnp.asarray(children_matrix(topo))
    maxd = int(topo.depths.max()) if topo.num_nodes else 0
    T = topo.num_nodes

    if store.is_paged:
        def row_prep(tp, dp, t_segs, t_len, d_segs, d_len, pages_row, pending,
                     active, accept_fn):
            rebatch = lambda segs: kvstore.map_segments(
                segs, lambda a: a, lambda a: a[:, None])
            t_caches = {"segments": rebatch(t_segs), "length": t_len,
                        "pages": pages_row[None]}
            d_caches = {"segments": rebatch(d_segs), "length": d_len,
                        "pages": pages_row[None]}
            with jax.named_scope("ssv.draft"):
                tokens, node_q, d_updates = draft_lib.expand_tree(
                    lambda caches, tk, pos, tm, par: model.verify_step(
                        dp, dcfg, caches, tk, pos, tm, par, None),
                    dcfg, d_caches, topo, pending[None],
                    temperature=temperature)
            with jax.named_scope("ssv.verify"):
                positions = (depths[None] + t_len).astype(jnp.int32)
                logits, t_updates = model.verify_step(
                    tp, tcfg, t_caches, tokens, positions, tmask[None],
                    parents, ssv)
            with jax.named_scope("ssv.accept"):
                path, out_tokens, bonus, n_acc = accept_fn(
                    tokens[0], logits[0], node_q[0])
            with jax.named_scope("ssv.commit"):
                n_commit = jnp.where(active, n_acc + 1, 0)[None]
                t_prep, t_new_len = model.commit_paged_prepare(
                    tp, tcfg, t_caches, t_updates, path[None], n_commit)
                d_prep, d_new_len = model.commit_paged_prepare(
                    dp, dcfg, d_caches, d_updates, path[None], n_commit)
            strip = lambda tree: jax.tree.map(lambda a: a[:, 0], tree)
            return (strip(t_prep), t_new_len, strip(d_prep), d_new_len,
                    out_tokens, n_acc)

        if greedy:
            def row_step(tp, dp, t_segs, t_len, d_segs, d_len, pages_row,
                         pending, active):
                return row_prep(tp, dp, t_segs, t_len, d_segs, d_len,
                                pages_row, pending, active, lambda tk, lg, _q:
                                accept_lib.greedy_tree_accept_device(
                                    child_mat, maxd, tk, lg))
            extra_axes = ()
        else:
            def row_step(tp, dp, t_segs, t_len, d_segs, d_len, pages_row,
                         pending, active, accept_u, bonus_u):
                return row_prep(tp, dp, t_segs, t_len, d_segs, d_len,
                                pages_row, pending, active, lambda tk, lg, q:
                                accept_lib.stochastic_tree_accept_device(
                                    child_mat, maxd, tk, lg, q, accept_u,
                                    bonus_u, temperature))
            extra_axes = (0, 0)

        def ssv_batched_step(tp, dp, t_segs, t_len, d_segs, d_len, pages,
                             pending, active, admit_mask, admit_len,
                             admit_pending, *rest):
            with jax.named_scope("ssv.admit_reset"):
                t_len = jnp.where(admit_mask, admit_len, t_len)
                d_len = jnp.where(admit_mask, admit_len, d_len)
                pending = jnp.where(admit_mask, admit_pending, pending)
            # pool leaves are shared (unmapped); every other cache leaf is
            # row-batched on axis 1 as in the dense step
            t_axes = kvstore.map_segments(t_segs, lambda _: None, lambda _: 1)
            d_axes = kvstore.map_segments(d_segs, lambda _: None, lambda _: 1)
            vstep = jax.vmap(row_step,
                             in_axes=(None, None, t_axes, 0, d_axes, 0, 0, 0, 0)
                             + extra_axes,
                             out_axes=(1, 0, 1, 0, 0, 0))
            (t_prep, t_new_len, d_prep, d_new_len, out_tokens,
             n_acc) = vstep(tp, dp, t_segs, t_len, d_segs, d_len, pages,
                            pending, active, *rest)
            with jax.named_scope("ssv.commit"):
                n_commit = jnp.where(active, n_acc + 1, 0)
                new_t = model.commit_apply_paged(t_segs, t_prep, pages, t_len,
                                                 n_commit)
                new_d = model.commit_apply_paged(d_segs, d_prep, pages, d_len,
                                                 n_commit)
            return new_t, t_new_len, new_d, d_new_len, out_tokens, n_acc

        return jax.jit(ssv_batched_step, donate_argnums=(2, 3, 4, 5))

    def row_core(tp, dp, t_segs, t_len, d_segs, d_len, pending, active,
                 accept_fn):
        t_caches = {"segments": jax.tree.map(lambda a: a[:, None], t_segs),
                    "length": t_len}
        d_caches = {"segments": jax.tree.map(lambda a: a[:, None], d_segs),
                    "length": d_len}
        with jax.named_scope("ssv.draft"):
            tokens, node_q, d_updates = draft_lib.expand_tree(
                lambda caches, tk, pos, tm, par: model.verify_step(
                    dp, dcfg, caches, tk, pos, tm, par, None),
                dcfg, d_caches, topo, pending[None], temperature=temperature)
        with jax.named_scope("ssv.verify"):
            positions = (depths[None] + t_len).astype(jnp.int32)
            logits, t_updates = model.verify_step(
                tp, tcfg, t_caches, tokens, positions, tmask[None], parents,
                ssv)
        with jax.named_scope("ssv.accept"):
            path, out_tokens, bonus, n_acc = accept_fn(tokens[0], logits[0],
                                                       node_q[0])
        with jax.named_scope("ssv.commit"):
            n_commit = jnp.where(active, n_acc + 1, 0)[None]
            new_t = model.commit(tp, tcfg, t_caches, t_updates, path[None],
                                 n_commit)
            new_d = model.commit(dp, dcfg, d_caches, d_updates, path[None],
                                 n_commit)
        return (jax.tree.map(lambda a: a[:, 0], new_t["segments"]),
                new_t["length"],
                jax.tree.map(lambda a: a[:, 0], new_d["segments"]),
                new_d["length"], out_tokens, n_acc)

    if greedy:
        def row_step(tp, dp, t_segs, t_len, d_segs, d_len, pending, active):
            return row_core(tp, dp, t_segs, t_len, d_segs, d_len, pending,
                            active, lambda tk, lg, _q:
                            accept_lib.greedy_tree_accept_device(
                                child_mat, maxd, tk, lg))
        in_axes = (None, None, 1, 0, 1, 0, 0, 0)
    else:
        def row_step(tp, dp, t_segs, t_len, d_segs, d_len, pending, active,
                     accept_u, bonus_u):
            return row_core(tp, dp, t_segs, t_len, d_segs, d_len, pending,
                            active, lambda tk, lg, q:
                            accept_lib.stochastic_tree_accept_device(
                                child_mat, maxd, tk, lg, q, accept_u,
                                bonus_u, temperature))
        in_axes = (None, None, 1, 0, 1, 0, 0, 0, 0, 0)

    vstep = jax.vmap(row_step, in_axes=in_axes, out_axes=(1, 0, 1, 0, 0, 0))

    def ssv_batched_step(tp, dp, t_segs, t_len, d_segs, d_len, pending,
                         active, admit_mask, admit_len, admit_pending, *rest):
        with jax.named_scope("ssv.admit_reset"):
            t_len = jnp.where(admit_mask, admit_len, t_len)
            d_len = jnp.where(admit_mask, admit_len, d_len)
            pending = jnp.where(admit_mask, admit_pending, pending)
        return vstep(tp, dp, t_segs, t_len, d_segs, d_len, pending, active,
                     *rest)

    return jax.jit(ssv_batched_step, donate_argnums=(2, 3, 4, 5))


@functools.partial(jax.jit, donate_argnums=(0,))
def admit_row_segments(batch_segs, row_segs, row):
    """Per-slot re-prefill landing: write a freshly-prefilled single-request
    cache (leaf batch axis of size 1) into row ``row`` of the batched cache
    pytree, in place (the batch buffers are donated — no copy of the other
    rows). ``row`` is a traced argument, so one compile serves every slot."""
    return jax.tree.map(
        lambda b, s: jax.lax.dynamic_update_slice_in_dim(
            b, s.astype(b.dtype), row, axis=1),
        batch_segs, row_segs)


# ------------------------------------------------- bucket-local group steps
class StepCompileCache:
    """Explicit AOT compile cache for the bucketed engine's fused group
    steps, keyed by (strategy, padded group size).

    jax.jit's implicit trace cache would retrace on first contact with every
    new (strategy, shape) pair — a multi-second stall that lands mid-serve
    exactly when the runtime guard switches a bucket's strategy. Entries here
    are ``.lower(...).compile()`` executables, populated either lazily (a
    recorded miss) or up front by ``BatchedSSVEngine.warmup``; hit/miss
    counts surface in the engine's kernel-cache metrics."""

    def __init__(self):
        self._exe: Dict = {}
        self.hits = 0
        self.misses = 0

    @property
    def size(self) -> int:
        return len(self._exe)

    def __contains__(self, key) -> bool:
        return key in self._exe

    def get_or_build(self, key, build):
        exe = self._exe.get(key)
        if exe is None:
            self.misses += 1
            exe = build()
            self._exe[key] = exe
        else:
            self.hits += 1
        return exe

    def stats(self) -> Dict[str, int]:
        return {"step_cache_hits": self.hits,
                "step_cache_misses": self.misses,
                "step_cache_cached": len(self._exe)}


@jax.jit
def _take_leaves(leaves, idx):
    """One fused dispatch gathering batch rows ``idx`` (axis 1) out of a
    list of row-batched cache leaves."""
    return [jnp.take(a, idx, axis=1) for a in leaves]


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _scatter_leaves(batch_leaves, group_leaves, ridx, r: int):
    """One fused, donated dispatch writing the first ``r`` group rows back
    into the batch leaves at rows ``ridx`` (axis 1). Padded duplicate rows
    past ``r`` are dropped — scattering them would race the real row."""
    return [b.at[:, ridx].set(
                jax.lax.slice_in_dim(g, 0, r, axis=1).astype(b.dtype))
            for b, g in zip(batch_leaves, group_leaves)]


def _pool_flags(segs, store: kvstore.KVStoreConfig):
    """Per-leaf booleans marking the paged store's shared-pool leaves (flat
    order aligned with ``jax.tree.flatten(segs)``)."""
    if not store.is_paged:
        return None
    flags = kvstore.map_segments(segs, lambda _: True, lambda _: False)
    return jax.tree.flatten(flags)[0]


def gather_group_segments(segs, idx, store: kvstore.KVStoreConfig):
    """Gather one execution group's rows out of a batched cache pytree.

    Dense: every leaf is row-batched on axis 1 — the group's KV rows are
    copied out in one fused dispatch (and written back by
    ``scatter_group_segments``). Paged: the shared page pool passes through
    BY REFERENCE — no KV copy; only the row-batched leaves (cmp / recurrent
    state, 16x smaller than raw KV) are gathered, and each row reads the
    pool through its own page-table row."""
    flat, treedef = jax.tree.flatten(segs)
    pool = _pool_flags(segs, store)
    if pool is None:
        return jax.tree.unflatten(treedef, _take_leaves(flat, idx))
    rows = [a for a, p in zip(flat, pool) if not p]
    taken = iter(_take_leaves(rows, idx))
    return jax.tree.unflatten(
        treedef, [a if p else next(taken) for a, p in zip(flat, pool)])


def scatter_group_segments(batch_segs, group_segs, ridx, r: int,
                           store: kvstore.KVStoreConfig):
    """Land a stepped group back into the batched cache pytree (only the
    ``r`` real rows; padding duplicates are dropped). Row-batched leaves are
    written with one fused, donated dispatch. The paged pool leaf is
    REPLACED wholesale: the group step committed into the shared (donated)
    pool in place, so its output is the batch's new pool — the stale pool
    leaf inside ``batch_segs`` was consumed by that donation and is never
    touched here."""
    flat_b, treedef = jax.tree.flatten(batch_segs)
    flat_g = jax.tree.flatten(group_segs)[0]
    pool = _pool_flags(batch_segs, store)
    if pool is None:
        return jax.tree.unflatten(treedef,
                                  _scatter_leaves(flat_b, flat_g, ridx, r))
    rows_b = [a for a, p in zip(flat_b, pool) if not p]
    rows_g = [a for a, p in zip(flat_g, pool) if not p]
    written = iter(_scatter_leaves(rows_b, rows_g, ridx, r))
    return jax.tree.unflatten(
        treedef, [g if p else next(written)
                  for g, p in zip(flat_g, pool)])


class BatchedSSVEngine:
    """True multi-request SSV engine: one device launch per step serves the
    whole batch, with per-request committed lengths, per-request acceptance,
    and completion masks. Requests are prefilled independently (exact
    per-prompt caches) and their cache pytrees stacked along the batch axis.

    Continuous batching: ``start_empty`` allocates a fixed number of batch
    slots up front; ``admit`` re-prefills one request into a freed slot
    (donated in-place row write + per-row admission mask on the next fused
    step) without perturbing in-flight rows; ``serve_continuous`` runs the
    full queue → admit → step loop against a ``schedule.Scheduler``.

    The verification strategy is shared within one fused launch (the tree
    topology must be uniform for vectorization). A ``RuntimePlanner``
    observes the mean acceptance over active rows and switches ONE strategy
    for the whole batch; a ``planner_lib.BatchPlanner`` instead partitions
    the live slots into context-regime execution groups and
    ``serve_continuous`` launches one fused ``step_group`` per group under
    that bucket's profile strategy — mixed-length batches stop paying a
    one-size-fits-all topology (see the bucketed paragraph on
    ``serve_continuous``).
    """

    def __init__(self, target_params, target_cfg: ModelConfig, draft_params,
                 draft_cfg: ModelConfig, serve_cfg: ServeConfig, planner=None,
                 rng_seed: int = 0):
        self.tp, self.tcfg = target_params, target_cfg
        self.dp, self.dcfg = draft_params, draft_cfg
        self.serve = serve_cfg
        self.planner = planner
        self.rng = np.random.default_rng(rng_seed)
        self.t_segs = self.d_segs = None
        self.t_len = self.d_len = None
        self.pending: Optional[np.ndarray] = None
        self.committed_len: Optional[np.ndarray] = None  # host-side (R,)
        self.batch = 0
        # pending per-row admission resets, consumed by the next step()
        self._admit_mask: Optional[np.ndarray] = None
        self._admit_len: Optional[np.ndarray] = None
        self._admit_pending: Optional[np.ndarray] = None
        self._compiles_at_freeze = -1
        # KV store backend: one page pool + one page table serve BOTH models
        # (same logical token positions per row; per-model pools differ only
        # in head geometry / layer count)
        self.store = _resolve_store(serve_cfg, target_cfg)
        self.allocator: Optional[kvstore.PageAllocator] = None
        self.pages: Optional[np.ndarray] = None          # (R, max_pages) host
        self._slot_pages: Dict[int, np.ndarray] = {}
        if self.store.is_paged:
            self._page_size = self.store.page_size
            self._max_pages = self.store.logical_pages(serve_cfg.max_context,
                                                       self._page_size)
        # bucket-local execution groups: AOT-compiled per-(strategy, padded
        # group size) fused steps; see step_group / warmup
        self.step_cache = StepCompileCache()
        self._step_cache_slots: Optional[int] = None

    # -------------------------------------------------------------- setup
    def _planner_begin(self, context_len: int):
        """Reset the attached planner for a fresh serving run: a BatchPlanner
        resets its per-bucket guards, a RuntimePlanner re-seeds from the
        batch's context regime."""
        if self.planner is None:
            return
        if getattr(self.planner, "is_batch_planner", False):
            self.planner.begin_serve()
        else:
            self.planner.begin_request(context_len=context_len)

    def _max_gamma(self) -> int:
        return max_draft_gamma(self.serve, self.planner)

    def _step_headroom(self) -> int:
        return step_headroom(self.serve, self.planner)

    def _check_prompt(self, p: np.ndarray, what: str = "prompt"):
        if len(p) == 0:
            raise ValueError(f"{what} is empty — need at least 1 token")
        # the generate loops stop a row once committed_len + headroom reaches
        # max_context, but only AFTER its first step — a prompt admitted
        # without that headroom would let the first commit write past the
        # cache end (XLA clamps the slice -> silent KV corruption), so the
        # bound must hold at admission time, over every strategy the planner
        # could switch to.
        headroom = self._step_headroom()
        if len(p) - 1 + headroom > self.serve.max_context:
            raise ValueError(
                f"{what} has {len(p)} tokens, exceeding "
                f"max_context={self.serve.max_context} minus the "
                f"{headroom}-token speculative-step headroom; truncate the "
                f"prompt or raise ServeConfig.max_context")

    def _reset_admission(self, R: int):
        self._admit_mask = np.zeros((R,), bool)
        self._admit_len = np.zeros((R,), np.int32)
        self._admit_pending = np.zeros((R,), np.int32)

    # ------------------------------------------------------------ page math
    def pages_for(self, prompt_len: int, max_new_tokens: int = 0) -> int:
        """Full-life page reservation for one request — see
        ``request_pages`` (shared with the single-stream engine)."""
        return request_pages(self.serve, self.planner, self._page_size,
                             self._max_pages, prompt_len, max_new_tokens)

    def _free_slot_pages(self, slot: int):
        pg = self._slot_pages.pop(slot, None)
        if pg is not None:
            self.allocator.free(pg)
            self.pages[slot] = -1

    def kv_cache_bytes(self) -> int:
        """Raw-KV footprint of the serving caches (both models) — dense:
        slots x max_context rows; paged: the shared page pools."""
        return (kvstore.kv_cache_bytes(self.t_segs)
                + kvstore.kv_cache_bytes(self.d_segs))

    def kernel_cache_stats(self) -> Dict[str, int]:
        """Engine cache metrics next to ``kv_cache_bytes``: process-wide
        kernel build / layout caches plus this engine's group-step AOT
        compile cache."""
        stats = kernel_cache_stats()
        stats.update(self.step_cache.stats())
        return stats

    # --------------------------------------------- group-step compile cache
    def _padded_group_sizes(self) -> List[int]:
        """The batch sizes a group launch can take: powers of two up to the
        slot count (plus the slot count itself). Execution groups are padded
        up to the next size so the compile cache holds O(log slots) shapes
        per strategy instead of one per arbitrary group size."""
        sizes, g = [], 1
        while g < self.batch:
            sizes.append(g)
            g *= 2
        sizes.append(self.batch)
        return sizes

    def _group_step_specs(self, ssv: SSVConfig, g: int) -> List:
        """Abstract (shape, dtype) argument list of the fused step for a
        ``g``-row execution group — what ``.lower`` needs to AOT-compile it
        without touching real buffers. Derived from the live caches, so it
        matches ``step_group``'s gathered arguments exactly."""
        spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        row_spec = lambda a: jax.ShapeDtypeStruct(
            a.shape[:1] + (g,) + a.shape[2:], a.dtype)
        if self.store.is_paged:
            segs_spec = lambda segs: kvstore.map_segments(segs, spec, row_spec)
        else:
            segs_spec = lambda segs: jax.tree.map(row_spec, segs)
        ivec = jax.ShapeDtypeStruct((g,), jnp.int32)
        bvec = jax.ShapeDtypeStruct((g,), jnp.bool_)
        args = [jax.tree.map(spec, self.tp), jax.tree.map(spec, self.dp),
                segs_spec(self.t_segs), ivec, segs_spec(self.d_segs), ivec]
        if self.store.is_paged:
            args.append(jax.ShapeDtypeStruct((g, self._max_pages), jnp.int32))
        args += [ivec, bvec, bvec, ivec, ivec]
        if self.serve.temperature != 0.0:
            topo = build_topology(ssv.tree_depth, ssv.tree_width,
                                  ssv.traversal, ssv.tree_budget)
            maxd = int(topo.depths.max()) if topo.num_nodes else 0
            kmax = max(1, children_matrix(topo).shape[1])
            args.append(jax.ShapeDtypeStruct((g, maxd + 1, kmax), jnp.float32))
            args.append(jax.ShapeDtypeStruct((g,), jnp.float32))
        return args

    def _compiled_group_step(self, ssv: SSVConfig, g: int):
        """The AOT-compiled fused step for a ``g``-row group under ``ssv``,
        from the explicit compile cache (lazy-compile on miss)."""
        greedy = self.serve.temperature == 0.0
        key = (ssv, int(g))

        def build():
            fn = jit_batched_step(self.tcfg, self.dcfg, ssv, greedy,
                                  self.serve.temperature, self.store)
            return fn.lower(*self._group_step_specs(ssv, g)).compile()

        return self.step_cache.get_or_build(key, build)

    def warmup(self, num_slots: Optional[int] = None,
               strategies: Optional[Sequence[SSVConfig]] = None) -> int:
        """Opt-in AOT warmup: compile the fused group step for every
        (strategy, padded group size) bucketed serving can launch, so a
        mid-serve strategy switch — or a group size first seen mid-flight —
        lands on a ready executable instead of stalling the whole batch on a
        retrace. ``strategies`` defaults to the attached BatchPlanner's
        reachable set (per bucket: the top rank plus every refinement hop the
        guard can take). Returns the number of executables compiled."""
        if strategies is None:
            if not getattr(self.planner, "is_batch_planner", False):
                raise ValueError(
                    "warmup compiles the bucketed group-step cache: attach a "
                    "planner_lib.BatchPlanner (profile-backed) or pass the "
                    "strategies to compile explicitly")
            strategies = self.planner.reachable_strategies()
        if self.t_segs is None or (num_slots is not None
                                   and num_slots != self.batch):
            self.start_empty(num_slots or self.serve.max_batch)
        before = self.step_cache.size
        for ssv in strategies:
            for g in self._padded_group_sizes():
                self._compiled_group_step(ssv, g)
        return self.step_cache.size - before

    def start(self, prompts: Sequence[np.ndarray]):
        R = len(prompts)
        if R < 1:
            raise ValueError("prompt list is empty — nothing to serve")
        prompts = [np.asarray(p) for p in prompts]
        for i, p in enumerate(prompts):
            self._check_prompt(p, what=f"prompt {i}")
        if self.store.is_paged:
            # one code path for every paged admission: empty slots + the
            # per-slot admit that allocates the row's pages
            self.start_empty(R)
            for i, p in enumerate(prompts):
                self.admit(i, p)
            self._planner_begin(int(np.max([len(p) for p in prompts])))
            return
        max_len = self.serve.max_context
        t_parts, d_parts = [], []
        for p in prompts:
            toks = jnp.asarray(np.asarray(p), jnp.int32)[None]
            _, tc = jit_prefill(self.tcfg, max_len)(self.tp, toks[:, :-1])
            _, dc = jit_prefill(self.dcfg, max_len)(self.dp, toks[:, :-1])
            t_parts.append(tc)
            d_parts.append(dc)

        def stack(parts):
            segs = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                                *[c["segments"] for c in parts])
            length = jnp.stack([c["length"] for c in parts])
            return segs, length

        self.t_segs, self.t_len = stack(t_parts)
        self.d_segs, self.d_len = stack(d_parts)
        self.pending = np.array([int(p[-1]) for p in prompts], np.int32)
        self.committed_len = np.array([len(p) - 1 for p in prompts], np.int64)
        self.batch = R
        self._reset_admission(R)
        self._planner_begin(int(np.max([len(p) for p in prompts])))

    def start_empty(self, num_slots: int):
        """Allocate ``num_slots`` empty batch slots (zeroed caches, length 0).
        Every request — including the first wave — then enters through
        ``admit``, so admitted-mid-flight rows share one code path."""
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if self.store.is_paged and self._step_cache_slots != num_slots:
            # the shared pool's physical size follows the slot count, so
            # group-step executables compiled for another slot count are
            # stale; dense group shapes are slot-count independent
            self.step_cache = StepCompileCache()
        self._step_cache_slots = num_slots
        max_len = self.serve.max_context
        self.t_segs = model.init_caches(self.tcfg, num_slots, max_len,
                                        self.store)["segments"]
        self.d_segs = model.init_caches(self.dcfg, num_slots, max_len,
                                        self.store)["segments"]
        self.t_len = jnp.zeros((num_slots,), jnp.int32)
        self.d_len = jnp.zeros((num_slots,), jnp.int32)
        self.pending = np.zeros((num_slots,), np.int32)
        self.committed_len = np.zeros((num_slots,), np.int64)
        self.batch = num_slots
        self._reset_admission(num_slots)
        if self.store.is_paged:
            self.allocator = kvstore.PageAllocator(
                self.store.resolved_num_pages(num_slots, self._max_pages))
            self.pages = np.full((num_slots, self._max_pages), -1, np.int32)
            self._slot_pages = {}

    # -------------------------------------------------------------- admission
    def admit(self, slot: int, prompt: np.ndarray, max_new_tokens: int = 0):
        """Mid-flight admission: re-prefill ``prompt`` and write its fresh KV
        prefix into batch row ``slot`` (donated in-place row write — other
        rows' cache bytes are untouched). The device-side length and pending
        root of the row are reset by the NEXT fused step via the per-row
        admission mask, so admission costs one prefill plus one row write,
        and no extra device launch.

        Paged store: admission first allocates the request's pages (see
        ``pages_for`` — ``max_new_tokens`` bounds the reservation) and maps
        them into the slot's page-table row; the prompt KV is then scattered
        into those pages. Callers gate on free-page headroom (the scheduler
        does) — admitting past the pool raises rather than corrupting rows.

        NOTE: the prefill jit retraces per prompt LENGTH — the first
        admission at a previously-unseen length pays an XLA compile while
        in-flight rows wait. Serving traffic with many distinct lengths
        should bucket/pad prompts to a few lengths."""
        if not 0 <= slot < self.batch:
            raise ValueError(f"slot {slot} out of range for batch {self.batch}")
        prompt = np.asarray(prompt)
        self._check_prompt(prompt)
        with obs.span("ssv.admit", slot=int(slot), prompt_len=len(prompt)):
            self._admit(slot, prompt, max_new_tokens)
        obs.count("ssv.admissions")
        obs.count("ssv.prefill_tokens", len(prompt) - 1)

    def _admit(self, slot: int, prompt: np.ndarray, max_new_tokens: int):
        max_len = self.serve.max_context
        toks = jnp.asarray(prompt, jnp.int32)[None]
        with obs.span("ssv.prefill", model="target"):
            _, tc = jit_prefill(self.tcfg, max_len)(self.tp, toks[:, :-1])
        with obs.span("ssv.prefill", model="draft"):
            _, dc = jit_prefill(self.dcfg, max_len)(self.dp, toks[:, :-1])
        if self.store.is_paged:
            with obs.span("ssv.kv.alloc"):
                self._free_slot_pages(slot)    # stale mapping of a past tenant
                need = self.pages_for(len(prompt), max_new_tokens)
                pg = self.allocator.alloc(need)
                if pg is None:
                    raise RuntimeError(
                        f"page pool exhausted admitting into slot {slot}: "
                        f"need {need} pages, {self.allocator.free_count} free "
                        "— gate admission on free-page headroom (Scheduler "
                        "pages_for)")
                self._slot_pages[slot] = pg
                row = np.full((self._max_pages,), -1, np.int32)
                row[:need] = pg
                self.pages[slot] = row
            with obs.span("ssv.kv.scatter"):
                rowj = jnp.asarray(row)
                self.t_segs = kvstore.admit_row_paged(
                    self.t_segs, tc["segments"], jnp.int32(slot), rowj)
                self.d_segs = kvstore.admit_row_paged(
                    self.d_segs, dc["segments"], jnp.int32(slot), rowj)
        else:
            with obs.span("ssv.kv.scatter"):
                self.t_segs = admit_row_segments(self.t_segs, tc["segments"],
                                                 slot)
                self.d_segs = admit_row_segments(self.d_segs, dc["segments"],
                                                 slot)
        self._admit_mask[slot] = True
        self._admit_len[slot] = len(prompt) - 1
        self._admit_pending[slot] = int(prompt[-1])
        self.pending[slot] = int(prompt[-1])
        self.committed_len[slot] = len(prompt) - 1

    # -------------------------------------------------------------- one step
    def step(self, active: np.ndarray,
             strategy: Optional[SSVConfig] = None) -> Tuple[np.ndarray, np.ndarray]:
        """active: (R,) bool — rows to advance. Returns (tokens (R, pad+1),
        n_accepted (R,)); inactive rows commit nothing (length frozen). Rows
        admitted since the last step have their device length / pending root
        reset inside this same launch (per-row admission mask), so the launch
        serves freshly-admitted and mid-generation rows together.

        Recorded (``repro.obs``) as an ``ssv.step`` span whose children are
        ``ssv.step.prepare`` (host arrays), ``.launch``, ``.sync`` (the wait
        for the device's tokens) and ``.update`` (host state)."""
        if strategy is None and getattr(self.planner, "is_batch_planner",
                                        False):
            raise ValueError(
                "a BatchPlanner has no single batch-wide strategy — pass "
                "strategy= explicitly, or serve through serve_continuous / "
                "step_group so each execution group gets its bucket's plan")
        ssv = strategy or (self.planner.current() if self.planner else self.serve.ssv)
        live = np.asarray(active, bool)
        rows = int(live.sum())
        with obs.span("ssv.step", rows=rows):
            toks_np, n_np = self._step(live, ssv)
        self._freeze_heap_after_compiles()
        obs.count("ssv.steps")
        obs.count("ssv.rows_stepped", rows)
        obs.count("ssv.tokens_committed", int((n_np + 1)[live].sum()))
        return toks_np, n_np

    def _freeze_heap_after_compiles(self):
        """After a step that followed a compile or a compile-cache load, move
        every live Python object to the collector's permanent generation
        (``gc.freeze``). Tracing and compiling leave a large heap that lives
        as long as the jit caches; a full collection walks all of it, and
        one that falls inside a later step's device wait holds the host, and
        so the idle chip, until it ends. Later full collections walk only
        what came after. Cyclic garbage alive at the freeze is never
        collected."""
        n = obs.backend_compiles()
        if n != self._compiles_at_freeze:
            self._compiles_at_freeze = n
            gc.freeze()

    def _step(self, live: np.ndarray, ssv: SSVConfig):
        with obs.span("ssv.step.prepare"):
            greedy = self.serve.temperature == 0.0
            step_fn = jit_batched_step(self.tcfg, self.dcfg, ssv, greedy,
                                       self.serve.temperature, self.store)
            args = [self.tp, self.dp, self.t_segs, self.t_len, self.d_segs,
                    self.d_len]
            if self.store.is_paged:
                args.append(jnp.asarray(self.pages))
            args += [jnp.asarray(self.pending), jnp.asarray(live),
                     jnp.asarray(self._admit_mask),
                     jnp.asarray(self._admit_len, jnp.int32),
                     jnp.asarray(self._admit_pending, jnp.int32)]
            self._admit_mask = np.zeros_like(self._admit_mask)
            if not greedy:
                topo = build_topology(ssv.tree_depth, ssv.tree_width,
                                      ssv.traversal, ssv.tree_budget)
                us = [accept_lib.draw_uniforms(topo, self.rng)
                      for _ in range(self.batch)]
                args.append(jnp.asarray(np.stack([u for u, _ in us]),
                                        jnp.float32))
                args.append(jnp.asarray([b for _, b in us], jnp.float32))
        with obs.span("ssv.step.launch"):
            (self.t_segs, self.t_len, self.d_segs, self.d_len, out_tokens,
             n_acc) = step_fn(*args)
        # per-step host transfer: (R, pad+1) token ids + (R,) counts
        with obs.span("ssv.step.sync"):
            toks_np = np.asarray(out_tokens)
            n_np = np.asarray(n_acc)
        with obs.span("ssv.step.update"):
            self.pending = np.where(live, toks_np[np.arange(self.batch), n_np],
                                    self.pending).astype(np.int32)
            self.committed_len = self.committed_len + np.where(live, n_np + 1,
                                                               0)
        return toks_np, n_np

    def step_group(self, rows: Sequence[int],
                   strategy: SSVConfig) -> Tuple[np.ndarray, np.ndarray]:
        """Advance one bucket-local execution group: gather ``rows`` out of
        the batch, run one fused step under ``strategy`` (from the AOT
        compile cache), scatter the results back. Every listed row is
        stepped (the per-row admission resets of freshly-admitted rows are
        consumed exactly like ``step``); rows outside the group are
        untouched — their cache bytes, lengths, and pending roots stay
        byte-identical, so different groups can run different strategies in
        the same serving round.

        The group is padded to the next cached group size with an INACTIVE
        duplicate of the first row (no-op commit; outputs dropped at
        scatter), keeping compiled shapes to O(log slots) per strategy. The
        paged store's page pool is threaded through shared and donated — no
        KV copy; dense groups pay one gather + one scatter of their rows.

        Returns (tokens (r, pad+1), n_accepted (r,)) aligned with ``rows``.
        """
        rows = [int(s) for s in rows]
        if not rows:
            raise ValueError("empty execution group — nothing to step")
        if len(set(rows)) != len(rows):
            raise ValueError(f"duplicate rows in execution group {rows}")
        for s in rows:
            if not 0 <= s < self.batch:
                raise ValueError(f"row {s} out of range for batch {self.batch}")
        with obs.span("ssv.step_group", rows=len(rows)):
            toks_np, n_np = self._step_group(rows, strategy)
        self._freeze_heap_after_compiles()
        obs.count("ssv.steps")
        obs.count("ssv.rows_stepped", len(rows))
        obs.count("ssv.tokens_committed", int((n_np + 1).sum()))
        return toks_np, n_np

    def _step_group(self, rows: List[int], strategy: SSVConfig):
        r = len(rows)
        # fast path: a group covering the whole batch (the common case under
        # the bucket-homogeneous admission policy) steps the engine caches
        # directly — donated in place, no gather/scatter at all
        full = rows == list(range(self.batch))
        with obs.span("ssv.step.prepare"):
            g = r if full else next(s for s in self._padded_group_sizes()
                                    if s >= r)
            pad_rows = rows + [rows[0]] * (g - r)
            active = np.zeros((g,), bool)
            active[:r] = True
            admit_mask = self._admit_mask[pad_rows].copy()
            admit_mask[r:] = False       # pads never reset the real row
            tail = ([jnp.asarray(self.pages[pad_rows])]
                    if self.store.is_paged else [])
            tail += [jnp.asarray(self.pending[pad_rows]), jnp.asarray(active),
                     jnp.asarray(admit_mask),
                     jnp.asarray(self._admit_len[pad_rows], jnp.int32),
                     jnp.asarray(self._admit_pending[pad_rows], jnp.int32)]
            self._admit_mask[rows] = False   # consumed by this launch
            if self.serve.temperature != 0.0:
                topo = build_topology(strategy.tree_depth, strategy.tree_width,
                                      strategy.traversal, strategy.tree_budget)
                us = [accept_lib.draw_uniforms(topo, self.rng)
                      for _ in range(g)]
                tail.append(jnp.asarray(np.stack([u for u, _ in us]),
                                        jnp.float32))
                tail.append(jnp.asarray([b for _, b in us], jnp.float32))
            step_fn = self._compiled_group_step(strategy, g)
        if full:
            t_grp, d_grp = self.t_segs, self.d_segs
            t_len_in, d_len_in = self.t_len, self.d_len
        else:
            with obs.span("ssv.group.gather"):
                idx = jnp.asarray(np.asarray(pad_rows, np.int32))
                t_grp = gather_group_segments(self.t_segs, idx, self.store)
                d_grp = gather_group_segments(self.d_segs, idx, self.store)
                t_len_in = jnp.take(self.t_len, idx)
                d_len_in = jnp.take(self.d_len, idx)
        with obs.span("ssv.step.launch"):
            (t_grp, t_len_g, d_grp, d_len_g, out_tokens,
             n_acc) = step_fn(self.tp, self.dp, t_grp, t_len_in, d_grp,
                              d_len_in, *tail)
        if full:
            self.t_segs, self.d_segs = t_grp, d_grp
            self.t_len, self.d_len = t_len_g, d_len_g
        else:
            with obs.span("ssv.group.scatter"):
                ridx = jnp.asarray(np.asarray(rows, np.int32))
                self.t_segs = scatter_group_segments(self.t_segs, t_grp, ridx,
                                                     r, self.store)
                self.d_segs = scatter_group_segments(self.d_segs, d_grp, ridx,
                                                     r, self.store)
                self.t_len = self.t_len.at[ridx].set(t_len_g[:r])
                self.d_len = self.d_len.at[ridx].set(d_len_g[:r])
        with obs.span("ssv.step.sync"):
            toks_np = np.asarray(out_tokens)[:r]
            n_np = np.asarray(n_acc)[:r]
        with obs.span("ssv.step.update"):
            self.pending[rows] = toks_np[np.arange(r), n_np].astype(np.int32)
            self.committed_len[rows] = self.committed_len[rows] + n_np + 1
        return toks_np, n_np

    # -------------------------------------------------------------- generate
    def generate_batch(self, prompts: Sequence[np.ndarray],
                       max_new_tokens: int = 0,
                       eos_id: int = -1) -> BatchGenerationResult:
        """Drain-mode batched generation: every prompt is admitted at t=0
        into its own slot and the batch runs to completion. Sugar over
        ``serve_continuous`` (one slot per prompt, no queue), so both entry
        points share one stepping/harvest loop."""
        if len(prompts) < 1:
            raise ValueError("prompt list is empty — nothing to serve")
        res = self.serve_continuous(
            [np.asarray(p) for p in prompts], num_slots=len(prompts),
            max_new_tokens=max_new_tokens, eos_id=eos_id)
        return BatchGenerationResult(results=res.results, steps=res.steps,
                                     wall_s=res.wall_s)

    # -------------------------------------------------------------- continuous
    def serve_continuous(self, requests: Sequence, num_slots: int,
                         max_new_tokens: int = 0, eos_id: int = -1,
                         bucketed: Optional[bool] = None,
                         warmup: bool = False) -> "ContinuousServeResult":
        """Continuous-batching serve loop: admit queued requests into freed
        slots mid-flight instead of draining the batch between waves.

        ``requests``: ``schedule.Request`` objects (arrival times on the
        virtual fused-step clock) or raw prompt arrays (all arrive at t=0).
        Per-row generation semantics are identical to single-stream
        ``SSVEngine.generate`` — admission never perturbs in-flight rows
        (tests/test_engine_continuous.py asserts token equality).

        Bucketed mode (``bucketed=None`` auto-enables it when the attached
        planner is a ``planner_lib.BatchPlanner``): each round, the live
        slots are partitioned into context-regime execution groups and one
        fused group step runs per group under the profile's strategy for
        that (bucket, precision class) — a mixed-length batch no longer
        forces short-context rows onto a long-context tree topology. The
        scheduler switches to the bucket-homogeneous admission policy, and
        per-row token streams stay byte-identical to single-stream
        generation under the row's bucket strategy
        (tests/test_engine_bucketed.py). ``warmup=True`` AOT-compiles every
        reachable (strategy, group size) step before serving starts.
        """
        max_new_default = max_new_tokens or self.serve.max_new_tokens
        is_bp = bool(getattr(self.planner, "is_batch_planner", False))
        if bucketed is None:
            bucketed = is_bp
        if bucketed and not is_bp:
            raise ValueError(
                "bucketed serving assigns each execution group its profile "
                "strategy — attach a planner_lib.BatchPlanner (built from an "
                "offline Profile); got "
                f"{type(self.planner).__name__ if self.planner else 'no planner'}")
        if is_bp and not bucketed:
            raise ValueError("a BatchPlanner only drives bucketed serving; "
                             "pass bucketed=True (or leave it None)")
        if warmup and not bucketed:
            raise ValueError("warmup=True pre-compiles the bucketed "
                             "group-step cache; it needs bucketed serving")
        reqs: List[schedule_lib.Request] = []
        for i, r in enumerate(requests):
            if isinstance(r, schedule_lib.Request):
                reqs.append(r)
            else:
                reqs.append(schedule_lib.Request(req_id=i,
                                                 prompt=np.asarray(r)))
        if not reqs:
            raise ValueError("request list is empty — nothing to serve")
        if len({r.req_id for r in reqs}) != len(reqs):
            raise ValueError("duplicate req_id in request list — outputs are "
                             "keyed by req_id and must not merge")
        for r in reqs:   # fail fast, before any slot state exists
            self._check_prompt(np.asarray(r.prompt),
                               what=f"request {r.req_id} prompt")
        sched_kwargs = {}
        if bucketed:
            sched_kwargs = dict(
                policy="bucket",
                bucket_of=lambda r: self.planner.bucket_of(len(r.prompt)))
        if self.store.is_paged:
            total_pages = self.store.resolved_num_pages(num_slots,
                                                        self._max_pages)
            pages_of = lambda r: self.pages_for(
                len(r.prompt), r.max_new_tokens or max_new_default)
            for r in reqs:   # a request bigger than the POOL can never admit
                if pages_of(r) > total_pages:
                    raise ValueError(
                        f"request {r.req_id} needs {pages_of(r)} KV pages but "
                        f"the pool has {total_pages}; raise kv_num_pages or "
                        "shrink the prompt/token budget")
            sched = schedule_lib.Scheduler(
                num_slots, pages_for=pages_of,
                free_pages=lambda: self.allocator.free_count,
                total_pages=total_pages, **sched_kwargs)
        else:
            sched = schedule_lib.Scheduler(num_slots, **sched_kwargs)
        for r in reqs:
            sched.submit(r)
        self.start_empty(num_slots)
        if bucketed:
            self.planner.begin_serve()
            if warmup:
                self.warmup()
        elif self.planner is not None:
            self.planner.begin_request(
                context_len=int(max(len(r.prompt) for r in reqs)))

        outs: Dict[int, List[int]] = {r.req_id: [] for r in reqs}
        step_logs: Dict[int, List[StepStats]] = {r.req_id: [] for r in reqs}
        occupancy: List[float] = []
        page_occupancy: List[float] = []
        bucket_occ: List[Dict[int, float]] = []
        group_launches = 0
        # context stop bound sized for the LARGEST strategy the planner can
        # switch to (a switch lands one step after this check runs)
        stop_margin = self._step_headroom()
        clock = 0.0
        n_steps = 0
        t_start = obs.now_ns()
        budget = sum((r.max_new_tokens or max_new_default) for r in reqs)
        safety = 4 * budget + 16 * len(reqs) + 16

        def harvest(slot, n, toks_row, dt, gamma, ssv):
            """Account one stepped row: record stats, stream its new tokens,
            and finish/release the slot at eos / budget / context bound.
            Shared verbatim by the single-launch and bucketed paths."""
            req = sched.request_at(slot)
            with obs.span("ssv.serve.harvest", req_id=req.req_id):
                out = outs[req.req_id]
                limit = req.max_new_tokens or max_new_default
                step_logs[req.req_id].append(StepStats(
                    accepted=n, emitted=n + 1, latency_s=dt, gamma=gamma,
                    strategy=ssv, host_elems=len(toks_row) + 1))
                finished = False
                for t in toks_row[: n + 1]:
                    out.append(int(t))
                    if int(t) == eos_id or len(out) >= limit:
                        finished = True
                        break
                if (self.committed_len[slot] + stop_margin
                        >= self.serve.max_context):
                    finished = True
                if finished:
                    sched.finish(slot, now=clock + 1.0)
                    if self.store.is_paged:
                        self._free_slot_pages(slot)   # pages return to pool
                    sched.release(slot)

        while not sched.idle():
            for slot, req in sched.admit(clock):
                with obs.span("ssv.serve.admit", req_id=req.req_id):
                    self.admit(slot, req.prompt, max_new_tokens=(
                        req.max_new_tokens or max_new_default))
                sched.mark_decoding(slot)
            active = sched.decoding_mask()
            if not active.any():
                # arrival gap (or page-gated head-of-line wait): jump the
                # virtual clock to the next arrival
                nxt = sched.next_arrival()
                clock = max(clock + 1.0,
                            float(nxt) if nxt is not None else clock + 1.0)
                continue
            occupancy.append(float(active.sum()) / num_slots)
            if self.store.is_paged:
                page_occupancy.append(sched.page_occupancy())
            if bucketed:
                bucket_occ.append(sched.bucket_occupancy())
                slot_buckets = {
                    int(s): self.planner.bucket_of(
                        len(sched.request_at(int(s)).prompt))
                    for s in np.nonzero(active)[0]}
                for bucket, rows in self.planner.plan(slot_buckets):
                    strat = self.planner.strategy_for(bucket)
                    gamma = build_topology(
                        strat.tree_depth, strat.tree_width, strat.traversal,
                        strat.tree_budget).num_nodes - 1
                    t0 = time.perf_counter()
                    toks_g, n_g = self.step_group(rows, strat)
                    dt = time.perf_counter() - t0
                    group_launches += 1
                    for j, slot in enumerate(rows):
                        harvest(slot, int(n_g[j]), toks_g[j], dt, gamma, strat)
                    self.planner.observe(bucket, accepted=float(np.mean(n_g)),
                                         latency_s=dt)
            else:
                ssv = (self.planner.current() if self.planner
                       else self.serve.ssv)
                gamma = build_topology(ssv.tree_depth, ssv.tree_width,
                                       ssv.traversal,
                                       ssv.tree_budget).num_nodes - 1
                t0 = time.perf_counter()
                toks, n_acc = self.step(active=active)
                dt = time.perf_counter() - t0
                accepted_active = []
                for slot in np.nonzero(active)[0]:
                    slot = int(slot)
                    n = int(n_acc[slot])
                    accepted_active.append(n)
                    harvest(slot, n, toks[slot], dt, gamma, ssv)
                if self.planner is not None and accepted_active:
                    self.planner.observe(
                        accepted=float(np.mean(accepted_active)),
                        latency_s=dt)
            clock += 1.0
            n_steps += 1
            if n_steps > safety:   # shapes guarantee progress; belt-and-braces
                break
        wall = (obs.now_ns() - t_start) / 1e9
        results = [GenerationResult(tokens=np.asarray(outs[r.req_id]),
                                    steps=step_logs[r.req_id]) for r in reqs]
        # mean decoding-slot fraction per bucket over the stepped rounds
        bucket_means: Dict[int, float] = {}
        if bucket_occ:
            for b in sorted({b for occ in bucket_occ for b in occ}):
                bucket_means[b] = float(
                    np.mean([occ.get(b, 0.0) for occ in bucket_occ]))
        return ContinuousServeResult(results=results, requests=reqs,
                                     steps=n_steps, wall_s=wall,
                                     occupancy=occupancy,
                                     page_occupancy=page_occupancy,
                                     kv_bytes=self.kv_cache_bytes(),
                                     bucket_occupancy=bucket_means,
                                     group_launches=group_launches,
                                     kernel_cache=self.kernel_cache_stats())


@dataclasses.dataclass
class ContinuousServeResult:
    """Outputs + serving statistics of a continuous-batching run. ``results``
    aligns with the submitted request order; queue-delay / occupancy are in
    virtual fused-step units (deterministic, wall-clock-free)."""
    results: List[GenerationResult]
    requests: List["schedule_lib.Request"]
    steps: int
    wall_s: float
    occupancy: List[float]       # per-fused-step busy-slot fraction
    # paged KV store only: per-fused-step allocated-page fraction + the raw
    # KV footprint of the run's caches (pool bytes; dense: row bytes)
    page_occupancy: List[float] = dataclasses.field(default_factory=list)
    kv_bytes: int = 0
    # bucketed serving only: mean decoding-slot fraction per context bucket
    # and the number of fused group launches issued (== steps when every
    # round had one homogeneous group)
    bucket_occupancy: Dict[int, float] = dataclasses.field(default_factory=dict)
    group_launches: int = 0
    # kernel-layer + group-step cache hit/miss counters at run end
    kernel_cache: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def total_tokens(self) -> int:
        return int(sum(len(r.tokens) for r in self.results))

    @property
    def aggregate_throughput(self) -> float:
        return self.total_tokens / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def mean_occupancy(self) -> float:
        return float(np.mean(self.occupancy)) if self.occupancy else 0.0

    @property
    def mean_page_occupancy(self) -> float:
        return float(np.mean(self.page_occupancy)) if self.page_occupancy else 0.0

    @property
    def peak_page_occupancy(self) -> float:
        return float(np.max(self.page_occupancy)) if self.page_occupancy else 0.0

    @property
    def mean_queue_delay_steps(self) -> float:
        delays = [r.queue_delay for r in self.requests
                  if r.queue_delay is not None]
        return float(np.mean(delays)) if delays else 0.0


# ------------------------------------------------------------ baselines
def autoregressive_decode(params, cfg: ModelConfig, prompt_tokens: np.ndarray,
                          max_new_tokens: int, max_context: int,
                          temperature: float = 0.0, seed: int = 0) -> GenerationResult:
    """Plain decode loop (the paper's 49 tok/s NSA baseline shape)."""
    toks = jnp.asarray(prompt_tokens, jnp.int32)[None]
    # prefill all but the last prompt token; the first decode step processes it
    _, caches = jit_prefill(cfg, max_context)(params, toks[:, :-1])
    step = jit_decode(cfg)
    rng = np.random.default_rng(seed)
    cur = jnp.asarray([[int(prompt_tokens[-1])]], jnp.int32)
    committed = len(prompt_tokens) - 1   # host-side length mirror, no sync
    out: List[int] = []
    steps: List[StepStats] = []
    for _ in range(max_new_tokens):
        t0 = time.perf_counter()
        logits, caches = step(params, caches, cur)
        lg = np.asarray(logits[0, 0], np.float32)
        if temperature == 0.0:
            nxt = int(lg.argmax())
        else:
            p = np.exp((lg - lg.max()) / temperature)
            nxt = int(rng.choice(len(p), p=p / p.sum()))
        dt = time.perf_counter() - t0
        out.append(nxt)
        steps.append(StepStats(accepted=0, emitted=1, latency_s=dt, gamma=0,
                               strategy=None))
        cur = jnp.asarray([[nxt]], jnp.int32)
        committed += 1
        if committed + 2 >= max_context:
            break
    return GenerationResult(tokens=np.asarray(out), steps=steps)
