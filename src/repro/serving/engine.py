"""Continuous-batching CoE serving engine over the paged KV pool.

The paper's deployment (§V-B, §VI-C) keeps the chip busy across expert
switches: requests are routed to experts, grouped, and the switching engine
hides DDR->HBM weight copies behind decode via next-expert prefetch. A
run-to-completion scheduler loses exactly that property under load — slots
idle while stragglers finish, and the queue waits for a full drain. This
engine instead keeps a persistent decode batch:

  * every decode slot's KV lives in ``PagedKVCache`` block tables — there is
    no dense per-group cache; admission, growth and recycling are block-table
    operations (``reserve``/``advance``/``free``);
  * one jit-compiled *paged extend* step (fixed ``(n_slots, g)`` shape,
    compiled once per engine) serves any subset of slots via an active-lane
    mask — inactive lanes scatter to the pool's scratch block;
  * per-step admission: newly-arrived requests for the active expert are
    prefilled into free slots while decode continues, so the batch refills
    the moment a slot recycles; when a group exhausts, the next expert is
    chosen preferring experts already resident in the ``HBMWeightCache``
    (switch = LRU hit); an aging counter admits any request stuck behind
    that preference, so no queued expert starves;
  * next-expert prefetch: each step the most-demanded non-resident expert is
    prefetched so the eventual switch overlaps decode (paper Fig 9);
  * decode policy is pluggable on the same slot machinery: ``GreedyDecode``
    (one token per round) or ``SpeculativeDecode`` (draft-verify, §VI-B).

``scheduler="run_to_completion"`` runs the OLD semantics — admit one expert
group, decode until every request completes, drain, repeat — on the same
paged substrate, so the two schedulers differ only in scheduling. That is
the baseline of ``benchmarks/run.py --sweep-arrival``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.coe import CompositionOfExperts
from repro.obs import flightrec, trace
from repro.obs.lifecycle import LifecycleTracker
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import SLOTracker
from repro.obs.stats import StatsView, counter_field
from repro.serving.kvcache import PagedKVCache, PrefixIndex
from repro.serving.prefill import (PackedPrefillRunner, PrefillHandoff,
                                   bucket_for, default_buckets, plan_packs)
from repro.serving.sessions import SessionManager
from repro.serving.speculative import SpecStats


@dataclass(eq=False)
class Request:
    rid: int
    tokens: np.ndarray          # (S,) prompt
    max_new_tokens: int
    arrival_s: float = field(default_factory=time.perf_counter)
    expert: Optional[str] = None        # routed at submit
    # prefill state computed off-engine (disaggregated prefill group);
    # admission adopts it into a slot instead of running a prefill
    handoff: Optional["PrefillHandoff"] = None
    prefill_done_s: Optional[float] = None
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None
    output: Optional[np.ndarray] = None
    skipped: int = 0                    # admission passes survived unadmitted
    # tenancy (serving/frontend.py + sessions.py): multi-turn session id
    # (retained KV adopted across turns), per-tenant accounting, SLO-aware
    # admission priority, and streaming callbacks. Callbacks run on the
    # engine's thread — keep them cheap (the frontend just enqueues).
    session_id: Optional[str] = None
    tenant: str = "default"
    priority: int = 0
    slo_ttft_s: Optional[float] = None
    slo_tpot_s: Optional[float] = None  # mean inter-token deadline (obs.slo)
    on_token: Optional[Callable[["Request", int], None]] = None
    on_done: Optional[Callable[["Request"], None]] = None
    prefix_hit_tokens: int = 0          # prompt tokens adopted, not prefilled
    # lifecycle-plane stamps/attribution (obs.lifecycle): the engine stamps
    # submit_s/admit_s/last_token_s; route_s is the router forward's cost;
    # switch_stall_s is activation time this request's admission paid;
    # preemptions counts frontend pull-backs from the engine queue
    submit_s: Optional[float] = None
    admit_s: Optional[float] = None
    last_token_s: Optional[float] = None
    route_s: float = 0.0
    switch_stall_s: float = 0.0
    preemptions: int = 0

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.done_s is None else self.done_s - self.arrival_s


@dataclass
class _Slot:
    req: Request
    expert: str
    last_token: int                     # next decode input
    generated: List[int]
    admitted_step: int

    @property
    def remaining(self) -> int:
        return self.req.max_new_tokens - len(self.generated)


class ServeStats(StatsView):
    """Engine counters as a view over the metrics registry (``serve.*``
    series). Field semantics unchanged from the old dataclass."""

    PREFIX = "serve"
    DERIVED = ("mean_occupancy",)

    requests = counter_field()
    tokens_out = counter_field()
    admitted = counter_field()
    decode_rounds = counter_field()
    switches = counter_field()
    starvation_overrides = counter_field()
    prefix_hit_tokens = counter_field()  # prompt tokens served from shared KV
    occupancy_sum = counter_field(0.0)  # Σ active_slots/n_slots per round
    route_s = counter_field(0.0)
    switch_s = counter_field(0.0)
    prefill_s = counter_field(0.0)
    exec_s = counter_field(0.0)
    table_uploads = counter_field()     # rounds that re-uploaded tables/lengths

    @property
    def mean_occupancy(self):
        return self.occupancy_sum / max(self.decode_rounds, 1)


# ----------------------------------------------------------------------
# Paged model execution (compiled once per (n_slots, g) shape).
# The step bodies and the runner live in serving/backends.py behind the
# backend seam; the names are re-exported here for compatibility.
# ----------------------------------------------------------------------

from repro.serving.backends import (PagedDecodeRunner, make_runner,  # noqa: E402
                                    xla_paged_extend as _paged_extend)


class _DeviceTableCache:
    """Cached device uploads of the per-slot block tables / lengths.

    The decode loop used to rebuild and re-upload ``tables``/``lengths``
    host arrays every round even when no slot changed. The pool versions
    its host bookkeeping (``table_version``/``length_version``), so the
    device copies are rebuilt only when the backing state moved or the
    slot->request mapping changed. Steady-state greedy rounds re-upload
    only lengths; the speculative draft loop (gamma extends against
    unchanged tables, lengths offset device-side) hits the cache for both.
    Cached arrays are never donated by the extend step (only the pool
    arrays are), so reuse across rounds is safe."""

    def __init__(self, pool: PagedKVCache, max_blocks: int,
                 empty_table: np.ndarray):
        self.pool = pool
        self.max_blocks = max_blocks
        self._empty = empty_table
        self._tab_key = None
        self._len_key = None
        self._tables = None
        self._lengths = None
        self.uploads = 0                # device uploads of either array

    def tables(self, rids: Tuple[Optional[int], ...]):
        key = (self.pool.table_version, rids)
        if key != self._tab_key:
            self._tables = jnp.asarray(np.stack([
                self.pool.padded_table(r, self.max_blocks)
                if r is not None else self._empty for r in rids]))
            self._tab_key = key
            self.uploads += 1
        return self._tables

    def lengths(self, rids: Tuple[Optional[int], ...]):
        key = (self.pool.length_version, rids)
        if key != self._len_key:
            self._lengths = jnp.asarray(np.array(
                [self.pool.length(r) if r is not None else 0 for r in rids],
                np.int32))
            self._len_key = key
            self.uploads += 1
        return self._lengths


@jax.jit
def sample(logits):
    """Greedy sampling: each lane's argmax at its last position."""
    return jnp.argmax(logits[:, -1], axis=-1)


# ----------------------------------------------------------------------
# Decode policies (pluggable on the slot machinery)
# ----------------------------------------------------------------------

class GreedyDecode:
    """One argmax token per active slot per round."""

    name = "greedy"
    reserve_slack = 0                   # extra tokens reserved beyond output

    def bind(self, engine: "ServingEngine"):
        self.engine = engine

    def on_admit(self, slot_idx: int, req: Request, params):
        pass

    def on_free(self, rid: int):
        pass

    def round(self, params, active: np.ndarray) -> Dict[int, List[int]]:
        eng = self.engine
        with trace.span("decode_inputs", cat="engine"):
            toks = np.zeros((eng.n_slots, 1), np.int32)
            for i in np.nonzero(active)[0]:
                # blocks were fully reserved at admission; only tokens needed
                toks[i, 0] = eng.slots[i].last_token
            tables, lengths = eng._device_tables()
            dact = eng._device_active(active)
        logits, pk, pv = eng.runner.extend(params, eng.pool.k, eng.pool.v,
                                           tables, lengths, dact, toks)
        eng.pool.k, eng.pool.v = pk, pv
        with trace.span("sample", cat="engine"):
            nxt = sample(logits)
        with trace.span("sync", cat="engine"):
            nxt = np.asarray(nxt, np.int32)
        return {int(i): [int(nxt[i])] for i in np.nonzero(active)[0]}


class SpeculativeDecode:
    """Draft-verify decode (paper §VI-B) on the paged slot machinery.

    A small shared draft expert proposes ``gamma`` tokens per slot; the
    target expert verifies all of them in ONE paged extend; the longest
    matching prefix plus one corrected token is emitted — with greedy
    acceptance the output is token-for-token identical to ``GreedyDecode``.
    The draft keeps its own paged pool, with block tables mirroring the
    target's slots. In a CoE the draft is simply another (small) composition
    member kept resident in HBM alongside the active target (§VI-B).

    Provisioning note: the draft pool (``d_pool``, same block count as the
    target pool but draft-sized blocks) and the draft weights are allocated
    IN ADDITION to the engine's pool — when planning an ``HBMBudget`` for a
    speculative deployment, count ``d_pool.capacity_bytes()`` and the draft
    weights against the tier yourself; the kv_reserve carve only covers the
    target pool.
    """

    name = "speculative"

    def __init__(self, draft_cfg: ModelConfig, draft_host_params,
                 gamma: int = 4):
        self.draft_cfg = draft_cfg
        self.gamma = gamma
        self.reserve_slack = gamma
        self._draft_host = draft_host_params
        self.stats = SpecStats()

    def bind(self, engine: "ServingEngine"):
        if self.draft_cfg.vocab_size != engine.cfg.vocab_size:
            raise ValueError("draft/target vocab mismatch")
        self.engine = engine
        self.d_params = jax.device_put(self._draft_host)
        self.d_pool = PagedKVCache(
            engine.pool.n_blocks, engine.block,
            self.draft_cfg.n_layers, self.draft_cfg.n_kv_heads,
            self.draft_cfg.head_dim, dtype=engine.pool.k.dtype, scratch=True)
        # the draft inherits the engine's backend, so a fused deployment
        # runs its single-token draft loop — the speculative hot path —
        # through the same Pallas kernels as the target
        self.d_runner = make_runner(self.draft_cfg, self.d_pool.scratch_index,
                                    backend=engine.runner.backend_name)
        self._d_dev = _DeviceTableCache(self.d_pool, engine.max_blocks,
                                        engine._empty_table)

    def on_admit(self, slot_idx: int, req: Request, params):
        # draft prefills the same prompt into its own pool
        self.d_pool.open(req.rid)
        _, k, v = self.d_runner.prefill_kv(self.d_params,
                                           jnp.asarray(req.tokens[None]))
        self.d_pool.append(req.rid, k, v)
        self.d_pool.reserve(req.rid, req.max_new_tokens + self.gamma)

    def on_free(self, rid: int):
        self.d_pool.free(rid)

    def round(self, params, active: np.ndarray) -> Dict[int, List[int]]:
        eng = self.engine
        B, g = eng.n_slots, self.gamma
        rows = np.nonzero(active)[0]
        cur = np.zeros((B, 1), np.int32)
        for i in rows:
            # both pools were fully reserved (incl. gamma slack) at admission
            cur[i, 0] = eng.slots[i].last_token

        tables, lengths = eng._device_tables()
        dact = eng._device_active(active)
        rids = eng._slot_rids()
        d_tables = self._d_dev.tables(rids)

        # --- draft proposes gamma tokens autoregressively
        props = np.zeros((B, g), np.int32)
        d_in = cur
        for t in range(g):
            lg, dk, dv = self.d_runner.extend(
                self.d_params, self.d_pool.k, self.d_pool.v,
                d_tables, lengths + t, dact, d_in)
            self.d_pool.k, self.d_pool.v = dk, dv
            d_in = np.asarray(jnp.argmax(lg[:, -1], -1), np.int32)[:, None]
            props[:, t] = d_in[:, 0]
            self.stats.draft_calls += 1

        # --- target verifies all gamma in one paged extend
        prop_inputs = np.concatenate([cur, props[:, :-1]], axis=1)   # (B,g)
        t_lg, pk, pv = eng.runner.extend(params, eng.pool.k, eng.pool.v,
                                         tables, lengths, dact, prop_inputs)
        eng.pool.k, eng.pool.v = pk, pv
        self.stats.target_calls += 1
        t_next = np.asarray(jnp.argmax(t_lg, -1), np.int32)          # (B,g)

        emits: Dict[int, List[int]] = {}
        for i in rows:
            match = props[i] == t_next[i]
            prefix = 0
            while prefix < g and match[prefix]:
                prefix += 1
            self.stats.proposed += g
            self.stats.accepted += prefix
            e = min(prefix + 1, g, eng.slots[i].remaining)
            emits[int(i)] = [int(x) for x in t_next[i, :e]]
            self.d_pool.advance(eng.slots[i].req.rid, e)
        return emits


# ----------------------------------------------------------------------
# The engine
# ----------------------------------------------------------------------

class ServingEngine:
    """Continuous-batching scheduler over the paged KV pool.

    ``step()`` is one scheduler iteration: pick/keep the active expert,
    admit newly-arrived requests into free slots (prefill), prefetch the
    next-most-demanded expert, run one decode round for the active expert's
    slots, recycle completed slots. ``drain()`` loops until idle.
    """

    def __init__(self, coe: CompositionOfExperts, cfg: ModelConfig, *,
                 max_len: int = 4096, n_slots: int = 8, block_size: int = 16,
                 kv_budget_bytes: Optional[int] = None,
                 policy=None, scheduler: str = "continuous",
                 switch_quantum: int = 8, starvation_limit: int = 16,
                 runner: Optional[PagedDecodeRunner] = None,
                 runner_factory=None,
                 backend: Optional[str] = None,
                 prefill_mode: str = "packed",
                 prefill_buckets: Optional[Sequence[int]] = None,
                 prefill_max_segments: Optional[int] = None,
                 prefix_sharing: bool = False,
                 session_max_bytes: Optional[int] = None,
                 kv_dtype=jnp.bfloat16,
                 registry: Optional[MetricsRegistry] = None,
                 obs_labels: Optional[Dict[str, Any]] = None):
        if scheduler not in ("continuous", "run_to_completion"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        if prefill_mode not in ("packed", "sequential"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        self.coe = coe
        self.cfg = cfg
        self.max_len = max_len
        self.n_slots = n_slots
        self.block = block_size
        self.scheduler = scheduler
        self.switch_quantum = switch_quantum
        self.starvation_limit = starvation_limit
        self.policy = policy or GreedyDecode()
        self.max_blocks = -(-(max_len + self.policy.reserve_slack)
                            // block_size)

        if kv_budget_bytes is None:
            # default: every slot can hold a full-length request, + scratch.
            # Under prefix sharing the index and retained sessions hold
            # blocks BETWEEN requests; sized only for the slots, retention
            # would compete with admission permanently (backpressure then
            # trickles admits in one at a time and decode occupancy
            # collapses), so the shared pool gets 2x the slot capacity —
            # retention lives in the slack and is still reclaimed, via the
            # pool's reclaimer protocol, whenever admission really needs it
            slot_blocks = self.n_slots * self.max_blocks
            pool_blocks = slot_blocks * (2 if prefix_sharing else 1) + 1
            kv_budget_bytes = coe.hbm_budget.kv_bytes or (
                pool_blocks * PagedKVCache.block_bytes(
                    block_size, cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                    kv_dtype))
        # one registry backs the engine's ServeStats and the pool's
        # PagedStats (private unless the caller publishes a shared one —
        # serve.py --metrics-port and RDUNode, which labels per group)
        self._registry = registry if registry is not None else MetricsRegistry()
        self._obs_labels = dict(obs_labels or {})
        self.pool = PagedKVCache.for_budget(
            kv_budget_bytes, block_size, cfg.n_layers, cfg.n_kv_heads,
            cfg.head_dim, kv_dtype, scratch=True,
            registry=self._registry, labels=self._obs_labels)
        self._empty_table = np.full((self.max_blocks,),
                                    self.pool.scratch_index, np.int32)
        # runner_factory lets a caller supply a runner that needs the pool's
        # scratch row without duplicating the pool-sizing logic above (the
        # node subsystem injects its tensor-parallel runner this way);
        # backend selects the decode-step implementation ('xla'/'fused',
        # see serving/backends.py) and is forwarded to the factory
        if runner is None:
            factory = runner_factory or PagedDecodeRunner
            kw = {} if backend is None else {"backend": backend}
            self.runner = factory(cfg, self.pool.scratch_index, **kw)
        else:
            if backend is not None and runner.backend_name != backend:
                raise ValueError(
                    f"shared runner executes backend "
                    f"{runner.backend_name!r}, engine asked for {backend!r}")
            self.runner = runner
        if self.runner.scratch_row != self.pool.scratch_index:
            raise ValueError(
                "shared runner was compiled for a different pool size "
                f"(scratch row {self.runner.scratch_row} != "
                f"{self.pool.scratch_index})")
        self._dev_tables = _DeviceTableCache(self.pool, self.max_blocks,
                                             self._empty_table)
        self._active_cache: Optional[Tuple[np.ndarray, jnp.ndarray]] = None
        # packed prefill: bucketed AOT-compiled forwards (serving/prefill.py)
        # shared by every expert; admission batches pending admits into one
        # packed call per (expert, bucket) instead of N sequential prefills.
        # "sequential" keeps the per-prompt prefill_kv path (one jit per
        # novel length — the recompile-stall baseline the benchmark sweeps).
        self.prefill_mode = prefill_mode
        if prefill_mode == "packed":
            self.prefill_runner: Optional[PackedPrefillRunner] = \
                PackedPrefillRunner(
                    cfg,
                    buckets=prefill_buckets or default_buckets(max_len),
                    max_segments=prefill_max_segments or n_slots)
        else:
            self.prefill_runner = None
        # copy-on-write prefix sharing + multi-turn session retention: a
        # PrefixIndex over the pool's blocks dedups prompts shared across
        # requests; a SessionManager keeps finished turns' pages resident
        # for the session's next turn. Both hold pool blocks speculatively
        # and hand them back under admission pressure via the pool's
        # reclaimer protocol — KV pages competing for the HBM tier exactly
        # like expert weights compete in the weight cache.
        self.prefix_sharing = prefix_sharing
        if prefix_sharing:
            self.sessions: Optional[SessionManager] = SessionManager(
                self.pool, ledger=coe.cache.ledger,
                max_bytes=session_max_bytes)
            self.prefix_index: Optional[PrefixIndex] = PrefixIndex(self.pool)
            # sessions reclaim first: one conversation's pages are cheaper
            # to lose than a prefix shared across many live sessions
            self.pool.add_reclaimer(self.sessions)
            self.pool.add_reclaimer(self.prefix_index)
            # suffix prefill rides the decode extend at these widths
            self._suffix_buckets: Tuple[int, ...] = tuple(
                prefill_buckets or default_buckets(max_len))
        else:
            self.sessions = None
            self.prefix_index = None
        # TTFT (arrival -> first token) was stored per request but never
        # aggregated; it now lands in a P2 streaming histogram. TPOT (mean
        # inter-token seconds after the first token) is the decode-side
        # half of the SLO pair and gets its own histogram.
        self._ttft_hist = self._registry.histogram("serve.ttft_s",
                                                   labels=self._obs_labels)
        self._tpot_hist = self._registry.histogram("serve.tpot_s",
                                                   labels=self._obs_labels)
        # request-lifecycle plane: per-request phase ledger + SLO/goodput
        # accounting, both fed at _finish (obs.lifecycle / obs.slo)
        self.lifecycle = LifecycleTracker(self._registry,
                                          labels=self._obs_labels)
        self.slo = SLOTracker(self._registry, labels=self._obs_labels)
        # /readyz readiness: False until warmup() AOT-compiled the hot path
        self.warmed = False
        # info-style gauge: which decode backend this engine executes
        self._registry.gauge("serve.backend", labels={
            **self._obs_labels,
            "backend": self.runner.backend_name}).set(1.0)
        self.policy.bind(self)

        self.queue: List[Request] = []
        self.slots: List[Optional[_Slot]] = [None] * n_slots
        self.stats = ServeStats(registry=self._registry,
                                labels=self._obs_labels)
        self._active_expert: Optional[str] = None
        self._quantum_used = 0
        self._step_count = 0

    # -- public API -------------------------------------------------------
    def submit(self, req: Request):
        """Enqueue a request. An untagged request (``expert=None``) is routed
        through the composition's router once, at arrival (§II); a request
        already tagged by an upstream router (e.g. the node scheduler) keeps
        its tag — routing happens exactly once either way."""
        if req.submit_s is None:         # keep the first stamp on re-submits
            req.submit_s = time.perf_counter()
        S = len(req.tokens)
        need = S + req.max_new_tokens + self.policy.reserve_slack
        if need > self.max_blocks * self.block:
            raise ValueError(
                f"request {req.rid}: {need} tokens exceed engine max_len "
                f"{self.max_len}")
        if -(-need // self.block) > self.pool.n_blocks:
            raise ValueError(
                f"request {req.rid} needs more KV blocks than the pool owns")
        if req.expert is None:
            with trace.span("route", cat="engine", request_id=req.rid) as sp:
                req.expert, dt = self.coe.route_request(req.tokens)
                sp.add(expert=req.expert)
            self.stats.route_s += dt
            req.route_s = dt
        elif req.expert not in self.coe.experts:
            raise KeyError(
                f"request {req.rid}: unknown expert {req.expert!r}")
        # one async lane per request: submit -> ... -> done (closed by
        # _finish, possibly many scheduler steps later)
        trace.async_begin("request", id=req.rid, cat="engine",
                          expert=req.expert, prompt_tokens=len(req.tokens))
        self.queue.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def step(self) -> List[Request]:
        """One scheduler iteration; returns requests completed in it."""
        self._step_count += 1
        done: List[Request] = []
        with trace.span("step", cat="engine", step=self._step_count) as sp:
            with trace.span("pick", cat="engine"):
                name = self._pick_expert()
            if name is None:
                return done
            if name != self._active_expert:
                self._switch_to(name)
            with trace.span("admission", cat="engine"):
                self._admit(done)
            with trace.span("prefetch_next", cat="engine"):
                self._prefetch_next()
            active = np.array([s is not None
                               and s.expert == self._active_expert
                               for s in self.slots], bool)
            if active.any():
                self._decode_round(active, done)
            self._quantum_used += 1
            self.stats.requests += len(done)
            sp.add(expert=self._active_expert, completed=len(done))
        return done

    def drain(self, max_steps: int = 1_000_000) -> List[Request]:
        """Run until queue and slots are empty; returns all completions.
        (Per-request pool-fit is enforced at ``submit``, so every queued
        request is eventually admissible and the loop terminates.)"""
        out: List[Request] = []
        steps = 0
        while self.has_work:
            out.extend(self.step())
            steps += 1
            if steps >= max_steps:
                raise RuntimeError("drain: exceeded max_steps")
        return out

    def warmup(self, expert: Optional[str] = None) -> None:
        """AOT-compile the serving hot path before traffic arrives: every
        packed-prefill bucket + its donated pool scatter, and the greedy
        decode extend for this engine's slot shape. All experts share the
        backbone, so compiling against one expert's params covers the whole
        composition — after this, a mixed-length greedy burst triggers zero
        XLA compilations (tests/test_prefill.py enforces it via the
        ``prefill.record_compile`` hook). Speculative deployments still pay
        the draft model's own first-shape compiles."""
        names = self.coe.expert_names()
        if not names:
            raise RuntimeError("warmup: no experts registered")
        name = expert if expert is not None else (self._active_expert
                                                 or names[0])
        t0 = time.perf_counter()
        params = self.coe.cache.activate(name)
        self.stats.switch_s += time.perf_counter() - t0
        with trace.span("warmup", cat="engine", expert=name):
            if self.prefill_runner is not None:
                self.prefill_runner.warmup(params, self.pool)
            # one all-inactive extend compiles + runs the (n_slots, 1) step;
            # garbage K/V lands in the scratch block, the pool arrays are
            # donated and reassigned exactly like a real round
            tables = jnp.asarray(np.stack([self._empty_table] * self.n_slots))
            lengths = jnp.zeros((self.n_slots,), jnp.int32)
            active = jnp.zeros((self.n_slots,), bool)
            toks = np.zeros((self.n_slots, 1), np.int32)
            logits, pk, pv = self.runner.extend(
                params, self.pool.k, self.pool.v, tables, lengths, active,
                toks)
            self.pool.k, self.pool.v = pk, pv
            sample(logits).block_until_ready()
            if self.prefix_sharing:
                # suffix prefill (prefix hits) runs the decode extend at
                # bucket widths — compile each all-inactive now so a hit
                # never pays a mid-traffic XLA compile
                for g in self._suffix_buckets:
                    toks = np.zeros((self.n_slots, g), np.int32)
                    logits, pk, pv = self.runner.extend(
                        params, self.pool.k, self.pool.v, tables, lengths,
                        active, toks)
                    self.pool.k, self.pool.v = pk, pv
                    logits.block_until_ready()
        self.warmed = True               # /readyz flips to 200

    # -- scheduling internals --------------------------------------------
    def _blocks_for(self, req: Request) -> int:
        need = (len(req.tokens) + req.max_new_tokens
                + self.policy.reserve_slack)
        return -(-need // self.block)

    def _planned_blocks(self, req: Request) -> int:
        # +1 headroom under sharing: adopting a shared partial tail block
        # can COW-split into one extra fresh block beyond the request's own
        # need (a hit otherwise needs strictly fewer fresh blocks)
        return self._blocks_for(req) + (1 if self.prefix_sharing else 0)

    def _avail_blocks(self) -> int:
        # retained sessions and indexed prefixes hand blocks back under
        # pressure — gating admission on the free list alone would wedge
        # the scheduler the moment retention fills the pool
        n = self.pool.free_blocks
        if self.prefix_sharing:
            n += self.pool.reclaimable_blocks()
        return n

    def _any_active(self) -> bool:
        return any(s is not None for s in self.slots)

    def _pick_expert(self) -> Optional[str]:
        occupied: Dict[str, List[_Slot]] = {}
        for s in self.slots:
            if s is not None:
                occupied.setdefault(s.expert, []).append(s)
        if self.scheduler == "run_to_completion":
            if occupied:
                return self._active_expert
            return self.queue[0].expert if self.queue else None
        if self._active_expert in occupied:
            # rotate ONLY among experts with slots ready to decode — leaving
            # a live batch for a queue-only expert would abandon admitted
            # work and thrash the weight cache; queue-only experts get in
            # via admission prebatching or the starvation override.
            others = [e for e in occupied if e != self._active_expert]
            if self._quantum_used < self.switch_quantum or not others:
                return self._active_expert
            return min(others, key=lambda e: min(    # longest-waiting batch
                s.admitted_step for s in occupied[e]))
        if occupied:         # active expert drained: longest-waiting slots
            return min(occupied, key=lambda e: min(
                s.admitted_step for s in occupied[e]))
        if not self.queue:
            return None                  # no slots, no queue: idle
        # choose from the queue: starving first, then stall-free (resident
        # OR fully-landed prefetch — admission consults the async pipeline's
        # readiness, not just residency), then FIFO
        starving = [r for r in self.queue if r.skipped >= self.starvation_limit]
        if starving:
            self.stats.starvation_overrides += 1
            return starving[0].expert
        ready = [r for r in self.queue if self.coe.cache.ready(r.expert)]
        pick_from = ready or self.queue
        demand: Dict[str, int] = {}
        for r in pick_from:
            demand[r.expert] = demand.get(r.expert, 0) + 1
        return max(demand, key=demand.get)

    def _switch_to(self, name: str):
        t0 = time.perf_counter()
        with trace.span("switch", cat="engine", expert=name,
                        prev=self._active_expert):
            self._params = self.coe.cache.activate(name)
        dt = time.perf_counter() - t0
        self.stats.switch_s += dt
        flightrec.record("switch", expert=name, prev=self._active_expert,
                         stall_s=dt, **self._obs_labels)
        if self._active_expert is not None:
            self.stats.switches += 1
        self._active_expert = name
        self._quantum_used = 0

    def _admit(self, done: List[Request]):
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free or not self.queue:
            return
        if self.scheduler == "run_to_completion":
            if any(s is not None for s in self.slots):
                return                       # batch still running: no refill
            candidates = [r for r in self.queue
                          if r.expert == self._active_expert]
        else:
            # refill ONLY from the active expert's queue: one expert's
            # weights are live at a time, so a foreign-expert slot would sit
            # idle and shrink every decode batch it rides in. Other experts
            # get in when the active group exhausts (group selection in
            # _pick_expert prefers resident experts) — except requests aged
            # past the starvation limit, which are admitted unconditionally.
            starving = [r for r in self.queue
                        if r.skipped >= self.starvation_limit]
            active_reqs = [r for r in self.queue
                           if r.expert == self._active_expert
                           and r not in starving]
            candidates = starving + active_reqs
        # backpressure always admits at least one request while the engine
        # is otherwise idle: under sharing the conservative reclaimable
        # estimate can undercount cascade reclaim (session eviction exposes
        # index leaves), and stalling an idle engine would never recover
        if self.prefill_runner is None:
            admitted = []
            for r in candidates:
                if not free:
                    break
                if (self._planned_blocks(r) > self._avail_blocks()
                        and (admitted or self._any_active())):
                    break                    # KV backpressure: stop admitting
                if r.handoff is not None:
                    self._adopt_into_slot(free.pop(0), r, done)
                else:
                    m = self._match_prefix(r)
                    if m is not None:
                        t0 = time.perf_counter()
                        params = self.coe.cache.activate(r.expert)
                        if (r.expert != self._active_expert
                                and self._active_expert is not None):
                            self._params = self.coe.cache.activate(
                                self._active_expert)
                        dt = time.perf_counter() - t0
                        self.stats.switch_s += dt
                        r.switch_stall_s += dt
                        self._prefill_suffix([(r, m[0], m[1])], params,
                                             free, done)
                    else:
                        self._prefill_into_slot(free.pop(0), r, done)
                admitted.append(r)
        else:
            # packed admission: select this step's admits first (slot count
            # + planned-block backpressure, same break semantics as the
            # sequential loop), then run ONE packed prefill per
            # (expert, bucket-capacity chunk) instead of N sequential calls
            admitted = []
            planned = 0
            for r in candidates:
                if len(admitted) >= len(free):
                    break
                need = self._planned_blocks(r)
                if (planned + need > self._avail_blocks()
                        and (admitted or self._any_active())):
                    break                    # KV backpressure: stop admitting
                admitted.append(r)
                planned += need
            if admitted:
                self._admit_packed(admitted, free, done)
        if admitted:
            # age only requests passed over while the active group consumed
            # admission capacity — idle tail steps (free slots, nothing to
            # admit) are not preference and must not trip the override
            for r in self.queue:
                if r not in admitted:
                    r.skipped += 1
        self.queue = [r for r in self.queue if r not in admitted]

    def _prefill_into_slot(self, slot_idx: int, req: Request,
                           done: List[Request]):
        req.admit_s = time.perf_counter()
        trace.instant("admit", cat="engine", request_id=req.rid,
                      expert=req.expert, slot=slot_idx)
        flightrec.record("admit", rid=req.rid, expert=req.expert,
                         slot=slot_idx, **self._obs_labels)
        t0 = time.perf_counter()
        params = self.coe.cache.activate(req.expert)
        if (req.expert != self._active_expert
                and self._active_expert is not None):
            # a foreign (starving) admission may have evicted the decoding
            # expert; re-activate so residency, LRU order and the hit/miss
            # stats keep describing what is actually executing
            self._params = self.coe.cache.activate(self._active_expert)
        dt = time.perf_counter() - t0
        self.stats.switch_s += dt
        req.switch_stall_s += dt
        t0 = time.perf_counter()
        S = len(req.tokens)
        with trace.span("prefill", cat="engine", request_id=req.rid,
                        expert=req.expert, prompt_tokens=S,
                        **{"prefill.bucket": S, "prefill.packed": 0}):
            last, k, v = self.runner.prefill_kv(params,
                                                jnp.asarray(req.tokens[None]))
            first = jnp.argmax(last)
            with trace.span("sync", cat="engine"):
                first = int(first)
            self.pool.open(req.rid)
            self.pool.append(req.rid, k, v)
            # commit the request's whole block budget now so admission's
            # free_blocks check can never over-admit into mid-decode
            # exhaustion
            self.pool.reserve(req.rid,
                              req.max_new_tokens + self.policy.reserve_slack)
        self.stats.prefill_s += time.perf_counter() - t0
        # sequential prefill runs at the raw prompt length: the bucket
        # label IS the length (the packed path labels real buckets)
        self._registry.counter("serve.prefill_bucket", labels={
            **self._obs_labels, "bucket": S}).inc()
        self._slot_ready(slot_idx, req, int(first), params, done)

    def _admit_packed(self, reqs: List[Request], free: List[int],
                      done: List[Request]):
        """Admit this step's selected requests via packed prefill: adopt
        handed-off state first (no forward needed), then group the rest by
        expert (selection order preserved — starving before active) and run
        one packed call per bucket-capacity chunk."""
        now = time.perf_counter()
        for r in reqs:
            if r.admit_s is None:
                r.admit_s = now
        todo: List[Request] = []
        for r in reqs:
            if r.handoff is not None:
                self._adopt_into_slot(free.pop(0), r, done)
            else:
                todo.append(r)
        groups: Dict[str, List[Request]] = {}
        for r in todo:
            groups.setdefault(r.expert, []).append(r)
        foreign = False
        pr = self.prefill_runner
        for expert, rs in groups.items():
            t0 = time.perf_counter()
            params = self.coe.cache.activate(expert)
            dt = time.perf_counter() - t0
            self.stats.switch_s += dt
            for r in rs:                 # activation stall split pro rata
                r.switch_stall_s += dt / len(rs)
            if expert != self._active_expert:
                foreign = True
            # prefix hits prefill only their un-shared suffix (one extend
            # per n_slots-sized chunk); misses take the packed-bucket path
            hits: List[Tuple[Request, List[int], int]] = []
            misses: List[Request] = []
            for r in rs:
                m = self._match_prefix(r)
                if m is not None:
                    hits.append((r, m[0], m[1]))
                else:
                    misses.append(r)
            for c in range(0, len(hits), self.n_slots):
                self._prefill_suffix(hits[c:c + self.n_slots], params,
                                     free, done)
            for idx in plan_packs([len(r.tokens) for r in misses],
                                  pr.buckets, pr.max_segments):
                self._prefill_chunk([misses[i] for i in idx], params, free,
                                    done)
        if foreign and self._active_expert is not None:
            # a foreign (starving) admission may have evicted the decoding
            # expert; re-activate once for the whole batch (same invariant
            # as the sequential path, minus per-request churn)
            t0 = time.perf_counter()
            self._params = self.coe.cache.activate(self._active_expert)
            self.stats.switch_s += time.perf_counter() - t0

    def _prefill_chunk(self, reqs: List[Request], params, free: List[int],
                       done: List[Request]):
        """One packed prefill call: forward at the bucket shape, per-request
        pool bookkeeping, one donated scatter for the whole bucket."""
        for r in reqs:
            trace.instant("admit", cat="engine", request_id=r.rid,
                          expert=r.expert, slot=-1)
            flightrec.record("admit", rid=r.rid, expert=r.expert,
                             packed=len(reqs), **self._obs_labels)
        t0 = time.perf_counter()
        with trace.span("prefill", cat="engine",
                        request_ids=",".join(str(r.rid) for r in reqs),
                        expert=reqs[0].expert,
                        prompt_tokens=sum(len(r.tokens) for r in reqs),
                        **{"prefill.packed": len(reqs)}) as sp:
            res = self.prefill_runner(params, [r.tokens for r in reqs])
            sp.add(**{"prefill.bucket": res.bucket})
            firsts = jnp.argmax(res.logits[:len(reqs)], axis=-1)
            with trace.span("sync", cat="engine"):
                firsts = np.asarray(firsts, np.int32)
            # reserve prompt + whole output budget up front (same
            # over-admission guard as the sequential path)
            self.prefill_runner.scatter_into(
                self.pool, res, [r.rid for r in reqs],
                extra_tokens=[r.max_new_tokens + self.policy.reserve_slack
                              for r in reqs])
        self.stats.prefill_s += time.perf_counter() - t0
        self._registry.counter("serve.prefill_bucket", labels={
            **self._obs_labels, "bucket": res.bucket}).inc(len(reqs))
        for i, r in enumerate(reqs):
            self._slot_ready(free.pop(0), r, int(firsts[i]), params, done)

    def _match_prefix(
            self, req: Request) -> Optional[Tuple[List[int], int]]:
        """Longest reusable KV prefix for this request: its own session's
        retained pages first (the whole previous conversation — the longest
        possible match), then the cross-request prefix index. Returns
        PINNED ``(blocks, n_tokens)`` (``_prefill_suffix`` adopts then
        unpins) or ``None``."""
        if not self.prefix_sharing or req.handoff is not None:
            return None
        if len(req.tokens) < 2:
            return None      # nothing shareable: >= 1 suffix token must run
        if req.session_id is not None:
            m = self.sessions.adopt(req.session_id, req.expert, req.tokens)
            if m is not None:
                return m
        return self.prefix_index.match(req.expert, req.tokens)

    def _prefill_suffix(self,
                        items: List[Tuple[Request, List[int], int]],
                        params, free: List[int], done: List[Request]):
        """Admit prefix-hit requests by prefilling ONLY the un-shared
        suffix: each request is seated read-only on its adopted blocks
        (first tail write COW-splits) and the suffixes run through the
        decode extend at the smallest bucket covering the longest one —
        the shared tokens' forward is skipped entirely, the tentpole win.

        ``items`` holds up to ``n_slots`` ``(req, blocks, n_adopted)``
        triples for ONE expert, blocks pinned by ``_match_prefix``. Lanes
        past a short suffix write garbage K/V — beyond the reserved blocks
        it lands in table padding (the scratch row); inside the reserved
        slack it sits past the committed length, where decode overwrites
        before it ever attends (scatter-then-attend)."""
        t0 = time.perf_counter()
        lanes: List[Tuple[Request, int, int]] = []
        for req, blocks, n in items:
            if req.admit_s is None:
                req.admit_s = t0
            flightrec.record("admit", rid=req.rid, expert=req.expert,
                             prefix_hit=n, **self._obs_labels)
            self.pool.open(req.rid, adopt=blocks, adopt_len=n)
            self.pool.unpin(blocks)
            si = len(req.tokens) - n
            # whole remaining budget up front, same over-admission guard as
            # the full-prefill paths; reserve COW-splits a shared tail
            self.pool.reserve(req.rid, si + req.max_new_tokens
                              + self.policy.reserve_slack)
            lanes.append((req, n, si))
        g = bucket_for(max(si for _, _, si in lanes), self._suffix_buckets)
        toks = np.zeros((self.n_slots, g), np.int32)
        lengths = np.zeros((self.n_slots,), np.int32)
        tables = np.stack([self._empty_table] * self.n_slots)
        active = np.zeros((self.n_slots,), bool)
        for i, (req, n, si) in enumerate(lanes):
            toks[i, :si] = req.tokens[n:]
            lengths[i] = n
            tables[i] = self.pool.padded_table(req.rid, self.max_blocks)
            active[i] = True
        with trace.span("prefill_suffix", cat="engine",
                        request_ids=",".join(str(r.rid)
                                             for r, _, _ in lanes),
                        expert=lanes[0][0].expert,
                        shared_tokens=sum(n for _, n, _ in lanes),
                        **{"prefill.bucket": g,
                           "prefill.packed": len(lanes)}):
            logits, pk, pv = self.runner.extend(
                params, self.pool.k, self.pool.v, jnp.asarray(tables),
                jnp.asarray(lengths), jnp.asarray(active), toks)
            self.pool.k, self.pool.v = pk, pv
            nxt = jnp.argmax(logits, axis=-1)
            with trace.span("sync", cat="engine"):
                nxt = np.asarray(nxt, np.int32)
        self.stats.prefill_s += time.perf_counter() - t0
        self._registry.counter("serve.prefill_bucket", labels={
            **self._obs_labels, "bucket": g}).inc(len(lanes))
        for i, (req, n, si) in enumerate(lanes):
            self.pool.advance(req.rid, si)
            req.prefix_hit_tokens = n
            self.stats.prefix_hit_tokens += n
            self._slot_ready(free.pop(0), req, int(nxt[i, si - 1]),
                             params, done)

    def _adopt_into_slot(self, slot_idx: int, req: Request,
                         done: List[Request]):
        """Adopt prefill state computed by a disaggregated prefill group:
        append the handed-off K/V blocks into this engine's pool and seat
        the request as if it had just been prefilled locally. No forward
        runs and no expert activation is needed — the handoff already
        carries the first token."""
        trace.instant("admit", cat="engine", request_id=req.rid,
                      expert=req.expert, slot=slot_idx, handoff=1)
        h = req.handoff
        t0 = time.perf_counter()
        if req.admit_s is None:
            req.admit_s = t0
        flightrec.record("handoff", rid=req.rid, expert=req.expert,
                         slot=slot_idx, kv_bytes=h.nbytes(),
                         **self._obs_labels)
        with trace.span("adopt_handoff", cat="engine", request_id=req.rid,
                        expert=req.expert, prompt_tokens=len(req.tokens),
                        kv_bytes=h.nbytes()):
            self.pool.open(req.rid)
            self.pool.append(req.rid, jnp.asarray(h.k), jnp.asarray(h.v))
            self.pool.reserve(req.rid,
                              req.max_new_tokens + self.policy.reserve_slack)
        self.stats.prefill_s += time.perf_counter() - t0
        req.handoff = None                   # blocks landed; drop the copy
        self._slot_ready(slot_idx, req, h.first_token, None, done)

    def _slot_ready(self, slot_idx: int, req: Request, first: int, params,
                    done: List[Request]):
        """Shared admission tail: timestamps, TTFT histogram, slot seating,
        policy callback, immediate finish for max_new_tokens == 1."""
        now = time.perf_counter()
        if req.admit_s is None:              # paths that bypassed _admit
            req.admit_s = now
        if req.prefill_done_s is None:       # handoffs carry their own stamp
            req.prefill_done_s = now
        if req.first_token_s is None:
            req.first_token_s = now
            self._ttft_hist.observe(req.first_token_s - req.arrival_s)
        req.last_token_s = now               # watchdog stall baseline
        self.stats.admitted += 1
        self.stats.tokens_out += 1
        if req.on_token is not None:
            req.on_token(req, first)
        slot = _Slot(req=req, expert=req.expert, last_token=first,
                     generated=[first], admitted_step=self._step_count)
        # admit on the policy before any possible _finish: on_free must only
        # ever see rids that on_admit opened (e.g. the speculative draft pool)
        self.policy.on_admit(slot_idx, req, params)
        if slot.remaining == 0:              # max_new_tokens == 1
            self._finish(slot, done)
            return
        self.slots[slot_idx] = slot

    def _prefetch_next(self):
        """One-ahead prefetch of the next switch target so the eventual
        switch overlaps decode (paper §V-B / Fig 9): the longest-waiting
        foreign batch if one is ready (that is what rotation picks), else
        the most-demanded queued expert (that is what group selection
        picks). The load (store read + H2D copy) runs on the cache's
        background executor — this call never blocks the decode loop; the
        switch consumes the in-flight future via ``activate``. Already
        resident/in-flight -> nothing to do; prefetching anything else
        would just thrash the LRU cache."""
        waiting: Dict[str, int] = {}
        for s in self.slots:
            if s is not None and s.expert != self._active_expert:
                waiting[s.expert] = min(waiting.get(s.expert, 1 << 30),
                                        s.admitted_step)
        if waiting:
            name = min(waiting, key=waiting.get)
        else:
            demand: Dict[str, int] = {}
            for r in self.queue:
                if r.expert != self._active_expert:
                    demand[r.expert] = demand.get(r.expert, 0) + 1
            if not demand:
                return
            name = max(demand, key=demand.get)
        if self.coe.cache.resident(name):
            return
        need = self.coe.experts[name].nbytes
        active_bytes = (self.coe.experts[self._active_expert].nbytes
                        if self._active_expert else 0)
        if need + active_bytes <= self.coe.cache.capacity:
            self.coe.cache.prefetch(name)

    def _slot_rids(self) -> Tuple[Optional[int], ...]:
        return tuple(s.req.rid if s is not None else None
                     for s in self.slots)

    def _device_tables(self):
        """Device copies of the per-slot block tables and lengths, re-uploaded
        only when the pool bookkeeping or slot mapping changed (see
        ``_DeviceTableCache``)."""
        rids = self._slot_rids()
        dev = self._dev_tables
        before = dev.uploads
        out = dev.tables(rids), dev.lengths(rids)
        if dev.uploads != before:
            self.stats.table_uploads += 1
        return out

    def _device_active(self, active: np.ndarray):
        """Device copy of the active mask, reused while the mask is stable
        (steady-state decode keeps the same lanes active for many rounds)."""
        if (self._active_cache is None
                or not np.array_equal(self._active_cache[0], active)):
            self._active_cache = (active.copy(), jnp.asarray(active))
        return self._active_cache[1]

    def _decode_round(self, active: np.ndarray, done: List[Request]):
        t0 = time.perf_counter()
        with trace.span("decode", cat="engine", expert=self._active_expert,
                        active_slots=int(active.sum())):
            emits = self.policy.round(self._params, active)
        now = time.perf_counter()
        with trace.span("emit", cat="engine"):
            for i, toks in emits.items():
                slot = self.slots[i]
                n = len(toks)
                if n == 0:
                    continue
                self.pool.advance(slot.req.rid, n)
                slot.generated.extend(toks)
                slot.last_token = toks[-1]
                slot.req.last_token_s = now
                self.stats.tokens_out += n
                if slot.req.on_token is not None:
                    for t in toks:
                        slot.req.on_token(slot.req, int(t))
                if slot.remaining <= 0:
                    self._finish(slot, done)
                    self.slots[i] = None     # immediate slot recycling
        self.stats.exec_s += time.perf_counter() - t0
        self.stats.decode_rounds += 1
        self.stats.occupancy_sum += float(active.sum()) / self.n_slots

    def _finish(self, slot: _Slot, done: List[Request]):
        req = slot.req
        req.output = np.asarray(slot.generated[: req.max_new_tokens],
                                np.int32)
        req.done_s = time.perf_counter()
        req.last_token_s = req.done_s
        if self.prefix_sharing:
            # the pool holds KV for every *committed* position (the final
            # emitted token's KV was never written — decode stopped first),
            # so index/retain exactly that much of prompt + output
            seq = np.concatenate(
                [req.tokens, req.output])[: self.pool.length(req.rid)]
            self.prefix_index.insert(req.expert, seq,
                                     self.pool.table(req.rid))
            if req.session_id is not None:
                # retention takes over the rid; the session's next turn
                # adopts these pages instead of re-prefilling the history
                self.sessions.retain(req.session_id, req.rid, req.expert,
                                     seq)
            else:
                self.pool.free(req.rid)
        else:
            self.pool.free(req.rid)
        self.policy.on_free(req.rid)
        if req.on_done is not None:
            req.on_done(req)
        trace.async_end("request", id=req.rid, cat="engine",
                        tokens_out=len(req.output),
                        latency_s=req.latency_s)
        if len(req.output) > 1 and req.first_token_s is not None:
            self._tpot_hist.observe((req.done_s - req.first_token_s)
                                    / (len(req.output) - 1))
        self.lifecycle.complete(req)
        self.slo.observe(req)
        flightrec.record("done", rid=req.rid, expert=req.expert,
                         tokens_out=len(req.output), **self._obs_labels)
        done.append(req)

    # -- tenancy accounting ----------------------------------------------
    def release_shared(self) -> None:
        """Drop every retained session and indexed prefix (their pool
        references with them). After a drain this returns the pool to
        ``blocks_in_use == 0`` — the leak check of the tenancy tests."""
        if self.sessions is not None:
            self.sessions.evict_all()
        if self.prefix_index is not None:
            self.prefix_index.clear()

    def hbm_in_budget(self) -> bool:
        """Weights + live KV inside this engine's HBM tier right now: the
        weight cache within its capacity and — when the budget carves a KV
        share — the pool within that carve and the two tiers' live bytes
        within the total. Retained session pages and indexed prefixes count
        as live KV, which is the point: they compete with weights."""
        cache = self.coe.cache
        if cache.used_bytes > cache.capacity:
            return False
        b = self.coe.hbm_budget
        if b.kv_bytes:
            if self.pool.capacity_bytes() > b.kv_bytes:
                return False
            return (cache.used_bytes + self.pool.bytes_in_use()
                    <= b.total_bytes)
        return True

    # -- debug snapshots (/debug/* endpoints, flight-recorder state) -------
    def debug_slots(self) -> Dict[str, Any]:
        """Live decode-slot table: what every slot is doing right now."""
        now = time.perf_counter()
        slots = []
        for idx, s in enumerate(self.slots):
            if s is None:
                slots.append({"slot": idx, "state": "free"})
                continue
            r = s.req
            last = r.last_token_s or r.first_token_s or r.arrival_s
            slots.append({
                "slot": idx, "state": "decoding", "rid": r.rid,
                "expert": s.expert, "tenant": r.tenant,
                "generated": len(s.generated), "remaining": s.remaining,
                "since_last_token_s": now - last,
                "admitted_step": s.admitted_step})
        return {"active_expert": self._active_expert,
                "queue_depth": len(self.queue),
                "queued_rids": [r.rid for r in self.queue],
                "slots": slots}

    def debug_pool(self) -> Dict[str, Any]:
        """KV pool books: occupancy, refcounts, and the invariant audit."""
        p = self.pool
        return {"n_blocks": p.n_blocks, "block_size": p.block,
                "free_blocks": p.free_blocks,
                "blocks_in_use": p.stats.blocks_in_use,
                "shared_blocks": p.stats.shared_blocks,
                "bytes_in_use": p.bytes_in_use(),
                "capacity_bytes": p.capacity_bytes(),
                "open_rids": list(p.open_rids()),
                "reclaimable_blocks": p.reclaimable_blocks(),
                "invariant_violations": p.check_invariants()}

    def debug_sessions(self) -> Dict[str, Any]:
        """Retained-session table (empty when sessions are disabled)."""
        if self.sessions is None:
            return {"sessions": [], "bytes_retained": 0}
        sm = self.sessions
        return {"bytes_retained": sm.bytes_retained(),
                "max_bytes": sm.max_bytes,
                "evictions": sm.evictions,
                "sessions": [
                    {"sid": sid, "rid": s.rid, "expert": s.expert,
                     "tokens": int(len(s.tokens)), "last_use": s.last_use}
                    for sid, s in sm._sessions.items()]}

    def debug_providers(self) -> Dict[str, Any]:
        """Name -> zero-arg snapshot fn; serve.py mounts these on the
        metrics httpd (``/debug/<name>``) and registers them as flight-
        recorder state providers."""
        return {"slots": self.debug_slots, "pool": self.debug_pool,
                "sessions": self.debug_sessions}
