"""Unified telemetry (ISSUE 6): metrics registry, span tracing, ledger.

Covers: P² streaming-quantile accuracy vs exact percentiles, registry
get-or-create/label semantics, registry-backed stats views (the rewired
``SwitchStats``/``ServeStats``/... surface), the tier-transfer ledger,
span nesting + thread-safety, Chrome-trace schema validity for a real
engine drain, the disabled-tracing zero-allocation guard, failed-prefetch
stall attribution, and the Prometheus/JSON HTTP endpoint.
"""
import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
from repro.core.switching import HBMWeightCache, SwitchStats
from repro.models import get_model
from repro.obs import trace
from repro.obs.httpd import serve_metrics
from repro.obs.ledger import TransferLedger
from repro.obs.metrics import Histogram, MetricsRegistry, scoped
from repro.obs.stats import StatsView, as_dict, counter_field, gauge_field
from repro.obs.trace import NOOP_SPAN, Tracer, validate_chrome_trace
from repro.serving import Request, ServingEngine
from repro.store import HostMemoryStore


# ----------------------------------------------------------------------
# streaming quantiles
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dist", ["uniform", "lognormal"])
def test_p2_quantiles_match_exact_within_5pct(dist):
    rs = np.random.RandomState(0)
    xs = (rs.uniform(0.0, 10.0, 20000) if dist == "uniform"
          else rs.lognormal(0.0, 0.75, 20000))
    h = Histogram("lat")
    for x in xs:
        h.observe(float(x))
    for p in (0.5, 0.95, 0.99):
        exact = float(np.percentile(xs, p * 100))
        assert h.quantile(p) == pytest.approx(exact, rel=0.05), f"p{p}"
    s = h.summary()
    assert s["count"] == len(xs)
    assert s["min"] == pytest.approx(xs.min())
    assert s["max"] == pytest.approx(xs.max())
    assert s["mean"] == pytest.approx(xs.mean(), rel=1e-6)
    assert set(s) >= {"p50", "p95", "p99"}


def test_histogram_few_samples_falls_back_to_sorted():
    h = Histogram("lat")
    assert h.quantile(0.5) == 0.0          # empty
    for x in [3.0, 1.0, 2.0]:
        h.observe(x)
    assert h.quantile(0.5) == 2.0          # exact on <5 samples


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    c1 = reg.counter("x.hits")
    c1.inc(3)
    assert reg.counter("x.hits") is c1                 # get-or-create
    c2 = reg.counter("x.hits", {"group": "g0"})
    assert c2 is not c1                                # labels split series
    c2.inc()
    snap = reg.snapshot()
    assert snap["x.hits"] == 3
    assert snap["x.hits{group=g0}"] == 1
    with pytest.raises(TypeError):
        reg.gauge("x.hits")                            # kind mismatch
    text = reg.to_prometheus()
    assert "# TYPE x_hits counter" in text
    assert 'x_hits{group="g0"} 1' in text


def test_scoped_registry_isolation():
    from repro.obs.metrics import get_registry
    outer = get_registry()
    with scoped() as reg:
        assert get_registry() is reg
        reg.counter("only.inner").inc()
    assert get_registry() is outer
    assert "only.inner" not in outer.snapshot()


# ----------------------------------------------------------------------
# stats views
# ----------------------------------------------------------------------
class _ToyStats(StatsView):
    PREFIX = "toy"
    DERIVED = ("double",)

    hits = counter_field()
    lat_s = counter_field(0.0)
    depth = gauge_field()

    @property
    def double(self):
        return 2 * self.hits


def test_statsview_registry_backed():
    reg = MetricsRegistry()
    st = _ToyStats(registry=reg, labels={"group": "g1"}, hits=2)
    st.hits += 3
    st.lat_s += 0.25
    st.depth = 7
    # the same numbers are visible through the registry, no copying
    snap = reg.snapshot()
    assert snap["toy.hits{group=g1}"] == 5
    assert snap["toy.lat_s{group=g1}"] == 0.25
    assert snap["toy.depth{group=g1}"] == 7
    assert st.as_dict() == {"hits": 5, "lat_s": 0.25, "depth": 7,
                            "double": 10}
    st.reset()
    assert st.hits == 0 and reg.snapshot()["toy.hits{group=g1}"] == 0
    # reset keeps the same series object (benchmarks reuse views per phase)
    st.hits += 1
    assert reg.snapshot()["toy.hits{group=g1}"] == 1


def test_bare_statsviews_do_not_alias():
    a, b = _ToyStats(), _ToyStats()
    a.hits += 5
    assert b.hits == 0                     # private registry per bare view


def test_switchstats_as_dict_superset_of_seed_shape():
    seed_keys = {
        "hits", "misses", "prefetch_hits", "prefetches_issued",
        "prefetches_cancelled", "evictions", "drops", "bytes_copied_in",
        "bytes_copied_back", "bytes_copyback_elided", "switch_seconds",
        "stall_miss_seconds", "stall_prefetch_seconds",
        "store_read_seconds", "h2d_seconds", "copy_seconds",
        "overlap_ratio"}
    d = SwitchStats().as_dict()
    assert seed_keys <= set(d)             # baseline gate compatibility
    assert {"prefetch_failures", "stall_failed_prefetch_seconds"} <= set(d)


def test_shared_as_dict_handles_plain_dataclasses():
    from repro.store.base import StoreStats
    st = StoreStats()
    st.reads += 2
    d = as_dict(st)
    assert d["reads"] == 2 and "bytes_read" in d


# ----------------------------------------------------------------------
# transfer ledger
# ----------------------------------------------------------------------
def test_ledger_edges_stalls_and_overlap():
    reg = MetricsRegistry()
    led = TransferLedger(reg)
    led.record("store_read", 1000, 0.4, cause="miss", expert="e0")
    led.record("h2d", 1000, 0.6, cause="miss")
    led.note_stall(0.2, cause="miss")
    assert led.bytes_moved("store_read") == 1000
    assert led.copy_seconds == pytest.approx(1.0)
    assert led.stall_seconds == pytest.approx(0.2)
    assert led.overlap_ratio == pytest.approx(0.8)
    assert led.bandwidth_bps("h2d") == pytest.approx(1000 / 0.6)
    snap = reg.snapshot()
    assert snap["ledger.bytes{cause=miss,edge=store_read}"] == 1000
    assert snap["ledger.bytes_by_expert{expert=e0}"] == 1000
    assert snap["ledger.overlap_ratio"] == pytest.approx(0.8)
    assert snap["ledger.bandwidth_bps{edge=h2d}"] == pytest.approx(1000 / 0.6)
    led.reserve(512)
    assert led.reserved_bytes == 512
    led.release(512)
    assert led.reserved_bytes == 0
    d = led.as_dict()
    assert d["store_read_bytes"] == 1000 and d["overlap_ratio"] > 0
    with pytest.raises(ValueError):
        led.record("sideways", 1, 0.1)


# ----------------------------------------------------------------------
# tracing
# ----------------------------------------------------------------------
def test_disabled_tracing_is_allocation_free():
    tr = Tracer()
    assert tr.span("x") is NOOP_SPAN       # module-level singleton, no alloc
    assert tr.span("y", request_id=1) is NOOP_SPAN
    with tr.span("x") as sp:
        assert sp.add(k=1) is sp
    tr.instant("i")
    tr.async_begin("r", id=1)
    tr.async_end("r", id=1)
    assert tr.events() == []               # nothing recorded while disabled


def test_span_nesting_records_containment():
    tr = Tracer()
    tr.start()
    with tr.span("outer", cat="t"):
        time.sleep(0.002)
        with tr.span("inner", cat="t"):
            time.sleep(0.002)
        time.sleep(0.002)
    tr.stop()
    evs = {e["name"]: e for e in tr.events()}
    o, i = evs["outer"], evs["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1.0   # 1us slack
    assert o["dur"] >= i["dur"]


def test_trace_thread_safety_no_lost_events():
    tr = Tracer()
    tr.start()
    n_threads, n_spans = 8, 200
    barrier = threading.Barrier(n_threads)   # all threads alive at once

    def worker(k):
        barrier.wait()
        for j in range(n_spans):
            with tr.span("w", cat="t", thread=k, j=j):
                pass
            tr.instant("tick", cat="t", thread=k)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.stop()
    evs = tr.events()
    assert len(evs) == n_threads * n_spans * 2
    assert len({e["tid"] for e in evs}) == n_threads
    doc = tr.to_chrome_trace()
    assert validate_chrome_trace(doc) == []


def test_validator_flags_malformed_documents():
    assert validate_chrome_trace({}) == ["missing top-level 'traceEvents'"]
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0},  # no dur
        {"name": "q", "ph": "?", "pid": 1, "tid": 1, "ts": 0.0},  # bad phase
        {"name": "r", "ph": "e", "id": 9, "pid": 1, "tid": 1,
         "ts": 0.0},                                              # end<begin
        {"name": "r", "ph": "b", "id": 8, "pid": 1, "tid": 1,
         "ts": 0.0},                                              # unclosed
    ]}
    problems = validate_chrome_trace(bad)
    assert any("without dur" in p for p in problems)
    assert any("unknown phase" in p for p in problems)
    assert any("end before begin" in p for p in problems)
    assert any("unclosed" in p for p in problems)


# ----------------------------------------------------------------------
# engine integration: lifecycle spans + wall-clock accounting
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("samba-coe-expert-7b"))


def _mk_engine(cfg, n_experts=2, **kw):
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    experts = [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
               for i in range(n_experts)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    coe = CompositionOfExperts(HashRouter(n_experts), None,
                               int(2.5 * nbytes))
    for i, h in enumerate(experts):
        coe.register(ExpertHandle(f"e{i}", cfg, h))
    return ServingEngine(coe, cfg, max_len=32, n_slots=2, block_size=8, **kw)


def _mk_requests(cfg, n, new_tokens=4):
    rs = np.random.RandomState(0)
    return [Request(rid=i,
                    tokens=rs.randint(0, cfg.vocab_size, (8,)).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i in range(n)]


def test_engine_trace_covers_request_lifecycle(cfg, tmp_path):
    old = trace.set_tracer(Tracer())
    try:
        eng = _mk_engine(cfg)
        reqs = _mk_requests(cfg, 4)
        eng.submit(reqs[0])                # warm up jit outside the trace
        eng.drain()
        trace.enable()
        for r in reqs[1:]:
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.drain()
        wall = time.perf_counter() - t0
        trace.disable()
        assert len(done) == 3

        evs = trace.events()
        names = {e["name"] for e in evs}
        assert {"route", "step", "prefill", "decode", "admit",
                "request"} <= names
        # every submitted request opens and closes one async lane
        begins = {e["id"] for e in evs if e["ph"] == "b"
                  and e["name"] == "request"}
        ends = {e["id"] for e in evs if e["ph"] == "e"
                and e["name"] == "request"}
        assert begins == ends == {r.rid for r in reqs[1:]}

        # acceptance: step spans account for the drain's wall-clock
        step_s = sum(e["dur"] for e in evs
                     if e["name"] == "step" and e["ph"] == "X") / 1e6
        assert step_s == pytest.approx(wall, rel=0.10)

        # exported document is schema-valid Chrome trace JSON
        path = trace.export(tmp_path / "trace.json")
        doc = json.loads(path.read_text())
        assert validate_chrome_trace(doc) == []
        assert any(e["ph"] == "M" for e in doc["traceEvents"])
    finally:
        trace.set_tracer(old)


def _profile_spans(trace_dir):
    """``(name, start_ns, end_ns, thread)`` of the program's spans in the
    ``.xplane.pb`` that a profiler session wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for i, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.end_ns, f"{line.name}/{i}")
                       for e in line.events
                       if e.name.startswith(("engine.", "kernel.",
                                             "switch.")))
    return out


def test_engine_spans_land_in_the_profile(cfg, tmp_path):
    """With the ring tracer off and a profiler session recording, the
    engine's spans are written into the profile, on its clock, nested as
    the engine runs them."""
    old = trace.set_tracer(Tracer())
    try:
        eng = _mk_engine(cfg)
        reqs = _mk_requests(cfg, 4)
        eng.submit(reqs[0])                # compile outside the session
        eng.drain()
        jax.profiler.start_trace(str(tmp_path))
        try:
            for r in reqs[1:]:
                eng.submit(r)
            assert len(eng.drain()) == 3
        finally:
            jax.profiler.stop_trace()
        assert trace.events() == []        # the ring stayed off
    finally:
        trace.set_tracer(old)
    spans = _profile_spans(tmp_path)
    names = {s[0] for s in spans}
    assert {"engine.step", "engine.pick", "engine.admission",
            "engine.prefetch_next", "engine.prefill", "engine.decode",
            "engine.decode_inputs", "kernel.decode_kernel", "engine.sample",
            "engine.sync", "engine.emit"} <= names

    def inside(a, b):
        return b[3] == a[3] and b[1] <= a[1] and a[2] <= b[2]

    of = lambda n: [s for s in spans if s[0] == n]
    decodes, steps = of("engine.decode"), of("engine.step")
    assert decodes and all(any(inside(d, s) for s in steps)
                           for d in decodes)
    for child in ("engine.decode_inputs", "kernel.decode_kernel",
                  "engine.sample"):
        assert len(of(child)) == len(decodes)
        assert all(any(inside(c, d) for d in decodes) for c in of(child))
    # one blocking sync per decode round, inside it
    assert sum(any(inside(x, d) for d in decodes)
               for x in of("engine.sync")) == len(decodes)


# ----------------------------------------------------------------------
# failed-prefetch stall attribution (satellite of ISSUE 6)
# ----------------------------------------------------------------------
class _FailOnceStore(HostMemoryStore):
    def __init__(self):
        super().__init__()
        self.fail_next = False

    def get(self, name):
        if self.fail_next:
            self.fail_next = False
            raise IOError("transient capacity-tier read failure")
        return super().get(name)


def test_failed_prefetch_attribution_and_ledger():
    s = _FailOnceStore()
    s.put("e0", {"w": np.zeros(1024, np.float32)})
    cache = HBMWeightCache(1 << 20, store=s)
    s.fail_next = True
    assert cache.prefetch("e0") is True
    deadline = time.time() + 2.0
    while cache.inflight("e0") and not cache._inflight["e0"].done():
        assert time.time() < deadline
        time.sleep(0.005)
    cache.activate("e0")                   # waits on the doomed future,
    st = cache.stats                       # then retries inline as a miss
    assert st.prefetch_failures == 1
    assert st.stall_failed_prefetch_seconds > 0.0
    assert st.misses == 1 and st.prefetch_hits == 0
    # the wasted wait is NOT in the miss bucket (the pre-ISSUE-6 bug)
    assert st.switch_seconds == pytest.approx(
        st.stall_miss_seconds + st.stall_failed_prefetch_seconds, rel=1e-6)
    snap = cache.stats.registry.snapshot()
    assert snap["ledger.stall_seconds{cause=failed_prefetch}"] > 0.0
    assert snap["ledger.stall_seconds{cause=miss}"] > 0.0
    assert cache.ledger.reserved_bytes == 0    # reservation released
    cache.close()


def test_cache_publishes_ledger_and_gauges():
    s = HostMemoryStore()
    s.put("e0", {"w": np.zeros(4096, np.float32)})
    reg = MetricsRegistry()
    cache = HBMWeightCache(1 << 20, store=s, registry=reg,
                           labels={"group": "g0"})
    cache.activate("e0")
    snap = reg.snapshot()
    assert snap["switch.misses{group=g0}"] == 1
    assert snap["switch.hbm_used_bytes{group=g0}"] == cache.used_bytes
    assert snap["ledger.bytes{cause=miss,edge=store_read,group=g0}"] > 0
    assert cache.ledger.bytes_moved("h2d") == cache.stats.bytes_copied_in
    cache.close()


# ----------------------------------------------------------------------
# HTTP exposition
# ----------------------------------------------------------------------
def test_metrics_http_endpoints():
    reg = MetricsRegistry()
    reg.counter("serve.requests").inc(7)
    reg.histogram("serve.lat_s").observe(0.25)
    srv = serve_metrics(reg, port=0)
    try:
        text = urllib.request.urlopen(f"{srv.url}/metrics").read().decode()
        assert "serve_requests 7" in text
        assert "serve_lat_s_count 1" in text
        snap = json.loads(
            urllib.request.urlopen(f"{srv.url}/metrics.json").read())
        assert snap["serve.requests"] == 7
        assert snap["serve.lat_s:count"] == 1
        ok = urllib.request.urlopen(f"{srv.url}/healthz")
        assert ok.status == 200
    finally:
        srv.stop()
