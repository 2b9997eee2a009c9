"""Expert switching engine: the HBM tier as a software-managed LRU cache of
expert weights over the capacity tier (paper §V-B CoE runtime).

Mechanics reproduced from the paper:
  * LRU eviction when HBM capacity is hit;
  * read-only symbols (weights) skip copy-back to the capacity tier on
    eviction — only mutable state is written back (to the backing
    ``ExpertStore``);
  * per-model ahead-of-time size contracts (each compiled expert declares its
    HBM/DDR footprint before activation);
  * prefetch: a predicted-next expert is loaded on a background executor —
    store read + H2D copy both happen off the critical path, the analogue of
    the paper's §VII P2P/DDR streams running concurrently with compute.

The prefetch pipeline is double-buffered: at most ``max_inflight``
(default 2) loads ride the executor; issuing a prefetch beyond that cancels
the oldest unconsumed one (the newest prediction wins). ``activate``
consumes the in-flight future for its expert when one exists — blocking
only for whatever tail of the load has not finished yet ("hit under
prefetch") — and falls back to a synchronous load through the same pipeline
on a true miss. Per-phase timing is split into store-read seconds vs H2D
copy seconds (worker side) and ``switch_seconds`` (caller-side stall, the
Fig-1 "switch" bar).
"""
from __future__ import annotations

import time
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax

from repro.core.memory_tiers import MachineTiers, TPU_V5E_NODE
from repro.obs import trace
from repro.obs.ledger import TransferLedger
from repro.obs.metrics import MetricsRegistry
from repro.obs.stats import StatsView, counter_field
from repro.store import ExpertStore, HostMemoryStore


def tree_bytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


class SwitchStats(StatsView):
    """Switching-engine counters as a view over the metrics registry
    (``switch.*`` series). Field semantics unchanged from the old
    dataclass; ``as_dict`` keys are a superset of the old shape (the two
    ``failed-prefetch`` attribution fields are new)."""

    PREFIX = "switch"
    DERIVED = ("copy_seconds", "overlap_ratio")

    hits = counter_field()
    misses = counter_field()
    prefetch_hits = counter_field()   # activates served by in-flight prefetch
    prefetch_failures = counter_field()  # prefetch loads that died; retried as miss
    prefetches_issued = counter_field()
    prefetches_cancelled = counter_field()
    evictions = counter_field()
    drops = counter_field()           # explicit drop() retirements
    bytes_copied_in = counter_field()
    bytes_copied_back = counter_field()
    bytes_copyback_elided = counter_field()
    switch_seconds = counter_field(0.0)  # caller-side stall inside activate()
    stall_miss_seconds = counter_field(0.0)      # ...due to true misses
    stall_prefetch_seconds = counter_field(0.0)  # ...due to prefetch consumes
    stall_failed_prefetch_seconds = counter_field(0.0)  # ...waiting on a
    # prefetch future that then raised — previously silently folded into the
    # miss bucket, hiding the wasted prefetch-issue cost
    store_read_seconds = counter_field(0.0)  # capacity-tier read (worker side)
    h2d_seconds = counter_field(0.0)  # device_put + ready wait (worker side)

    @property
    def copy_seconds(self) -> float:
        """End-to-end load time (read + H2D), regardless of overlap."""
        return self.store_read_seconds + self.h2d_seconds

    @property
    def overlap_ratio(self) -> float:
        """Fraction of total load time hidden off the critical path.
        Clamped: caller-side stall includes bookkeeping/eviction time the
        worker-side phase timers don't see, so the raw ratio can dip below
        0 on miss-heavy runs."""
        total = self.copy_seconds
        if total <= 0:
            return 0.0
        return max(0.0, min(1.0, 1.0 - self.switch_seconds / total))


@dataclass
class _Entry:
    value: Any             # device pytree
    nbytes: int
    read_only: bool
    dirty: bool = False


@dataclass
class _Loaded:
    value: Any             # device pytree, ready
    nbytes: int
    read_s: float
    h2d_s: float


class _CallableStore(ExpertStore):
    """Adapter: a bare ``fetch(expert_id) -> host pytree`` callable as a
    read-only store (legacy constructor path)."""

    cheap_nbytes = False     # sizing requires a full fetch

    def __init__(self, fetch: Callable[[str], Any]):
        super().__init__()
        self._fetch = fetch

    def put(self, name, tree):
        raise NotImplementedError("fetch-callable store is read-only")

    def get(self, name):
        self._note_read(0)           # size unknown until fetched
        return self._fetch(name)

    def contains(self, name):
        return True                    # the callable decides; assume yes

    def delete(self, name):
        raise NotImplementedError

    def keys(self):
        return []

    def nbytes(self, name):
        return tree_bytes(self.get(name))


class HBMWeightCache:
    """LRU cache of expert parameter pytrees in device memory ("HBM"),
    backed by an ``ExpertStore`` capacity tier ("DDR").

    ``store.get(expert_id)`` is the DDR read; ``device_put`` is the
    DDR->HBM copy — both run on the prefetch executor. Dirty non-read-only
    entries are written back to the store (or the explicit ``writeback``
    callable) on eviction or ``drop``; read-only entries elide the
    copy-back (the paper's elision).
    """

    def __init__(self, capacity_bytes: int,
                 store: Optional[ExpertStore] = None,
                 fetch: Optional[Callable[[str], Any]] = None,
                 writeback: Optional[Callable[[str, Any], None]] = None,
                 device=None,
                 sharding=None,
                 max_inflight: int = 2,
                 registry: Optional[MetricsRegistry] = None,
                 labels: Optional[Dict[str, Any]] = None):
        if (store is None) == (fetch is None):
            raise ValueError("pass exactly one of store= or fetch=")
        self.capacity = int(capacity_bytes)
        self.store = store if store is not None else _CallableStore(fetch)
        if writeback is not None:
            self.writeback = writeback
        elif store is not None:
            self.writeback = store.put
        else:
            self.writeback = None
        self.device = device
        self.sharding = sharding
        self.max_inflight = max(1, int(max_inflight))
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self._inflight: "OrderedDict[str, Future]" = OrderedDict()
        self._reserved: dict = {}            # expert_id -> bytes held inflight
        self._pool: Optional[ThreadPoolExecutor] = None
        self._used = 0
        # stats view + tier-transfer ledger share one registry (a private
        # one unless the caller publishes into a shared registry — the node
        # scheduler labels each group's cache, serve.py the default one)
        registry = registry if registry is not None else MetricsRegistry()
        self.stats = SwitchStats(registry=registry, labels=labels)
        self.ledger = TransferLedger(registry, labels)
        self._hbm_used_gauge = registry.gauge("switch.hbm_used_bytes", labels)

    # -- internals -----------------------------------------------------
    def _executor(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.max_inflight,
                thread_name_prefix="hbm-prefetch")
        return self._pool

    def _put_device(self, host_tree):
        if self.sharding is not None:
            return jax.device_put(host_tree, self.sharding)
        if self.device is not None:
            return jax.device_put(host_tree, self.device)
        return jax.device_put(host_tree)

    def _read(self, expert_id: str):
        """Capacity-tier read: (host pytree, seconds)."""
        t0 = time.perf_counter()
        with trace.span("store_read", cat="switch", expert=expert_id):
            host = self.store.get(expert_id)
        return host, time.perf_counter() - t0

    def _h2d(self, expert_id: str, host, read_s: float) -> _Loaded:
        """Host -> HBM copy of one expert, waited to completion."""
        t0 = time.perf_counter()
        with trace.span("h2d", cat="switch", expert=expert_id):
            dev = self._put_device(host)
            jax.block_until_ready(dev)
        return _Loaded(dev, tree_bytes(host), read_s, time.perf_counter() - t0)

    def _load_job(self, expert_id: str) -> _Loaded:
        """Worker-side prefetch load: store read, then H2D copy into room
        the prefetch reserved up front. No shared-state mutation here — the
        consuming (caller) thread owns the books."""
        return self._h2d(expert_id, *self._read(expert_id))

    def _retire(self, name: str, entry: _Entry):
        """Account one entry leaving HBM (eviction or drop): write back
        dirty mutable state, elide the copy for read-only weights."""
        self._used -= entry.nbytes
        self._hbm_used_gauge.set(self._used)
        if entry.dirty and not entry.read_only and self.writeback is not None:
            t0 = time.perf_counter()
            with trace.span("writeback", cat="switch", expert=name):
                host = jax.device_get(entry.value)
                self.writeback(name, host)
            self.stats.bytes_copied_back += entry.nbytes
            self.ledger.record("writeback", entry.nbytes,
                               time.perf_counter() - t0, cause="dirty",
                               expert=name)
        else:
            self.stats.bytes_copyback_elided += entry.nbytes
            self.ledger.record("elided", entry.nbytes, cause="read_only",
                               expert=name)

    def _evict_one(self):
        name, entry = self._entries.popitem(last=False)     # LRU = oldest
        self.stats.evictions += 1
        self._retire(name, entry)

    def _make_room(self, need: int, *, strict: bool = True) -> bool:
        """Evict until ``need`` bytes fit inside the capacity NOT already
        reserved by in-flight loads. ``strict=False`` (the prefetch path)
        returns False instead of raising when the bytes cannot fit; the
        strict path (a demand miss) outranks speculation — it cancels
        stale in-flight prefetches to reclaim their reservations before
        giving up."""
        def budget():
            return self.capacity - sum(self._reserved.values())
        if need > budget():
            if not strict:
                return False
            while need > budget() and self._inflight:
                self.cancel(next(iter(self._inflight)))
            if need > budget():
                raise MemoryError(
                    f"expert of {need} bytes exceeds HBM tier capacity "
                    f"{self.capacity} (minus {self.capacity - budget()} "
                    f"bytes reserved by in-flight loads)")
        while self._used + need > budget():
            self._evict_one()
        return True

    def _unreserve(self, expert_id: str):
        need = self._reserved.pop(expert_id, None)
        if need:
            self.ledger.release(need)

    def _finish_load(self, expert_id: str, loaded: _Loaded, read_only: bool,
                     cause: str = "miss"):
        self._make_room(loaded.nbytes)
        self.stats.bytes_copied_in += loaded.nbytes
        self.stats.store_read_seconds += loaded.read_s
        self.stats.h2d_seconds += loaded.h2d_s
        self.ledger.record("store_read", loaded.nbytes, loaded.read_s,
                           cause=cause, expert=expert_id)
        self.ledger.record("h2d", loaded.nbytes, loaded.h2d_s, cause=cause)
        self._entries[expert_id] = _Entry(loaded.value, loaded.nbytes,
                                          read_only)
        self._used += loaded.nbytes
        self._hbm_used_gauge.set(self._used)
        return loaded.value

    # -- public API ------------------------------------------------------
    def resident(self, expert_id: str) -> bool:
        return expert_id in self._entries

    def inflight(self, expert_id: str) -> bool:
        return expert_id in self._inflight

    def ready(self, expert_id: str) -> bool:
        """Activating this expert would not stall: already in HBM, or its
        prefetch has fully landed *successfully* (admission consults this;
        a load that died with an exception will retry as a miss, which is
        a stall, so it must not report ready)."""
        if expert_id in self._entries:
            return True
        fut = self._inflight.get(expert_id)
        return (fut is not None and fut.done() and not fut.cancelled()
                and fut.exception() is None)

    @property
    def used_bytes(self) -> int:
        return self._used

    def activate(self, expert_id: str, *, read_only: bool = True):
        """Return the device pytree for an expert. Resident -> no stall;
        in-flight prefetch -> block only for the unfinished tail; true
        miss -> synchronous load through the same pipeline. The measured
        block time lands in ``stats.switch_seconds``."""
        if expert_id in self._entries:
            self._entries.move_to_end(expert_id)
            self.stats.hits += 1
            return self._entries[expert_id].value
        t0 = time.perf_counter()
        sp = trace.span("activate", cat="switch", expert=expert_id)
        sp.__enter__()
        fut = self._inflight.pop(expert_id, None)
        consumed_prefetch = False
        failed_wait_s = 0.0          # time sunk into a prefetch that raised
        loaded = None
        if fut is not None:
            self._unreserve(expert_id)
            try:
                loaded = fut.result()
                consumed_prefetch = True
                self.stats.hits += 1
                self.stats.prefetch_hits += 1
            except Exception:
                # failed prefetch load: retry as a miss — but the wait on
                # the doomed future is its own stall cause, not miss time
                # (previously folded into the miss bucket, hiding the
                # wasted prefetch-issue cost)
                failed_wait_s = time.perf_counter() - t0
                self.stats.prefetch_failures += 1
                self.stats.stall_failed_prefetch_seconds += failed_wait_s
                self.ledger.note_stall(failed_wait_s, cause="failed_prefetch")
                trace.instant("prefetch_failed", cat="switch",
                              expert=expert_id)
        if loaded is None:
            # true miss: load inline on the caller thread — submitting to
            # the (max_inflight-sized) executor would queue the critical
            # path behind in-flight prefetches of OTHER experts. Room is
            # made between the store read and the H2D copy, so HBM never
            # holds the tier's capacity plus the incoming expert
            self.stats.misses += 1
            host, read_s = self._read(expert_id)
            self._make_room(tree_bytes(host))
            loaded = self._h2d(expert_id, host, read_s)
        cause = "prefetch" if consumed_prefetch else (
            "failed_prefetch" if failed_wait_s else "miss")
        value = self._finish_load(expert_id, loaded, read_only, cause=cause)
        dt = time.perf_counter() - t0
        self.stats.switch_seconds += dt
        if consumed_prefetch:
            self.stats.stall_prefetch_seconds += dt
            self.ledger.note_stall(dt, cause="prefetch")
        else:
            miss_dt = dt - failed_wait_s
            self.stats.stall_miss_seconds += miss_dt
            self.ledger.note_stall(miss_dt, cause="miss")
        sp.add(outcome=cause, nbytes=loaded.nbytes)
        sp.__exit__(None, None, None)
        return value

    def prefetch(self, expert_id: str, *, read_only: bool = True) -> bool:
        """Issue an async load for a predicted-next expert; returns True if
        one was started. Never blocks: the store read and the H2D copy both
        run on the background executor and overlap in-flight compute
        (paper Fig 9 step overlap). ``read_only`` is advisory here — the
        entry's flag is set by the ``activate`` that consumes it."""
        if expert_id in self._entries or expert_id in self._inflight:
            return False
        while len(self._inflight) >= self.max_inflight:
            stale = next(iter(self._inflight))   # oldest prediction loses
            self.cancel(stale)
        # reserve HBM up front (size from the store's AOT manifest) so
        # concurrent in-flight loads can never over-commit the tier; a
        # prediction that cannot fit is skipped, not an error. Legacy
        # fetch-callable stores can only size an expert by fetching it —
        # a synchronous caller-thread read that would defeat the prefetch —
        # so they skip the reservation (pre-reservation semantics).
        if self.store.cheap_nbytes:
            try:
                need = self.store.nbytes(expert_id)
            except Exception:
                return False                 # unknown expert: nothing to do
            if not self._make_room(need, strict=False):
                return False
            self._reserved[expert_id] = need
            self.ledger.reserve(need)
        self._inflight[expert_id] = self._executor().submit(
            self._load_job, expert_id)
        self.stats.prefetches_issued += 1
        return True

    def cancel(self, expert_id: str) -> bool:
        """Cancel an in-flight prefetch. If the load already started on the
        worker, its result is discarded instead (never installed)."""
        fut = self._inflight.pop(expert_id, None)
        if fut is None:
            return False
        self._unreserve(expert_id)
        fut.cancel()
        self.stats.prefetches_cancelled += 1
        return True

    def mark_dirty(self, expert_id: str):
        self._entries[expert_id].dirty = True

    def drop(self, expert_id: str):
        """Explicitly retire an expert: cancel any in-flight prefetch and,
        for resident entries, write back dirty mutable state before
        releasing HBM (same books as eviction — previously this silently
        lost dirty state and skipped the stats)."""
        self.cancel(expert_id)
        if expert_id in self._entries:
            entry = self._entries.pop(expert_id)
            self.stats.drops += 1
            self._retire(expert_id, entry)

    def expert_ids(self):
        return list(self._entries.keys())

    def close(self):
        """Cancel pending prefetches and stop the executor. The cache stays
        usable — a later activate/prefetch restarts it lazily."""
        for expert_id in list(self._inflight):
            self.cancel(expert_id)
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None


def model_switch_time(nbytes: int, machine: MachineTiers = TPU_V5E_NODE) -> float:
    """Analytic switch latency: capacity-tier -> HBM copy at node bandwidth
    (paper Fig 1 / Fig 12: the DDR->HBM copy term)."""
    return nbytes / machine.copy_bw_node
