"""Property + behaviour tests for the paper's core: three-tier memory,
LRU switching, static allocator, CoE composition, bandwidth model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.core import (CompositionOfExperts, DGX_A100, DGX_H100, ExpertHandle,
                        HashRouter, HBMWeightCache, SN40L_NODE, Symbol,
                        allocate_static, plan_placement, spill_order)
from repro.core.bandwidth_model import (coe_latency, decode_step_cost,
                                        footprint_nodes, switch_cost)
from repro.core.fusion import model_fusion_report, plan
from repro.models import get_model


# ---------------------------------------------------------------- allocator
@st.composite
def _symbols(draw):
    n = draw(st.integers(1, 24))
    syms = []
    for i in range(n):
        first = draw(st.integers(0, 20))
        last = first + draw(st.integers(0, 10))
        size = draw(st.integers(1, 1 << 20))
        syms.append(Symbol(f"s{i}", size, first, last,
                           transfer_footprint=draw(st.integers(0, 1 << 22))))
    return syms


@given(_symbols())
@settings(max_examples=60, deadline=None)
def test_allocator_no_live_overlap(syms):
    """Symbols with overlapping lifetimes must never share addresses."""
    alloc = allocate_static(syms)
    al = 512
    rng = {s.name: (alloc.offsets[s.name],
                    alloc.offsets[s.name] + ((s.size + al - 1) // al) * al)
           for s in syms}
    for a in syms:
        for b in syms:
            if a.name >= b.name:
                continue
            lives_overlap = not (a.last_use < b.first_use or
                                 b.last_use < a.first_use)
            if lives_overlap:
                ra, rb = rng[a.name], rng[b.name]
                assert ra[1] <= rb[0] or rb[1] <= ra[0], (a, b, ra, rb)


@given(_symbols())
@settings(max_examples=30, deadline=None)
def test_allocator_peak_bounded_by_sum(syms):
    alloc = allocate_static(syms)
    total = sum(((s.size + 511) // 512) * 512 for s in syms)
    assert alloc.peak <= total


@given(_symbols())
@settings(max_examples=30, deadline=None)
def test_spill_order_is_bandwidth_ascending(syms):
    order = spill_order(syms)
    feet = [s.transfer_footprint for s in order]
    assert feet == sorted(feet)


def test_plan_placement_spills_until_fit():
    syms = [Symbol(f"w{i}", 1000, 0, 10, transfer_footprint=i * 100)
            for i in range(10)]
    alloc, spilled = plan_placement(syms, hbm_capacity=3 * 1024)
    assert alloc.peak <= 3 * 1024
    # lowest-footprint symbols spilled first
    assert spilled == [f"w{i}" for i in range(len(spilled))]


# ---------------------------------------------------------------- LRU cache
def _mk_host(nbytes=1024):
    return {"w": np.ones(nbytes // 4, np.float32)}


def test_lru_eviction_order_and_capacity():
    fetched = []
    cache = HBMWeightCache(3 * 1024, fetch=lambda n: (fetched.append(n),
                                                      _mk_host())[1])
    for name in ["a", "b", "c"]:
        cache.activate(name)
    assert cache.expert_ids() == ["a", "b", "c"]
    cache.activate("a")                      # refresh a
    cache.activate("d")                      # evicts b (LRU)
    assert "b" not in cache.expert_ids()
    assert cache.used_bytes <= cache.capacity
    assert cache.stats.evictions == 1
    assert cache.stats.bytes_copyback_elided > 0   # read-only elision


def test_lru_hit_no_refetch():
    calls = []
    cache = HBMWeightCache(1 << 20, fetch=lambda n: (calls.append(n),
                                                     _mk_host())[1])
    cache.activate("x")
    cache.activate("x")
    assert calls == ["x"]
    assert cache.stats.hits == 1


def test_prefetch_overlap_counts_no_recency():
    cache = HBMWeightCache(1 << 20, fetch=lambda n: _mk_host())
    cache.activate("a")
    assert cache.prefetch("b") is True
    assert cache.prefetch("b") is False      # already resident
    cache.activate("b")                      # hit after prefetch
    assert cache.stats.hits == 1
    assert cache.stats.misses == 1


def test_oversized_expert_raises():
    cache = HBMWeightCache(128, fetch=lambda n: _mk_host(4096))
    with pytest.raises(MemoryError):
        cache.activate("big")


def test_miss_evicts_before_h2d():
    """An inline miss makes room BEFORE its H2D copy: at the moment the
    incoming expert lands on the device, the resident bytes plus the
    incoming bytes fit the tier (never capacity plus one expert)."""
    cache = HBMWeightCache(2 * 1024, fetch=lambda n: _mk_host())
    put = cache._put_device
    peaks = []

    def watched_put(host):
        peaks.append(cache.used_bytes + sum(x.nbytes for x in
                                            jax.tree.leaves(host)))
        return put(host)

    cache._put_device = watched_put
    for name in ["a", "b", "c", "a", "d"]:
        cache.activate(name)
    assert cache.stats.misses == 5 and cache.stats.evictions == 3
    assert max(peaks) <= cache.capacity, peaks


@given(st.lists(st.integers(0, 5), min_size=1, max_size=40))
@settings(max_examples=30, deadline=None)
def test_lru_capacity_invariant(seq):
    cache = HBMWeightCache(2 * 1024, fetch=lambda n: _mk_host())
    for e in seq:
        cache.activate(f"e{e}")
        assert cache.used_bytes <= cache.capacity
        assert len(cache.expert_ids()) <= 2


# ---------------------------------------------------------------- router
@given(st.integers(1, 64), st.integers(1, 8), st.integers(2, 16))
@settings(max_examples=20, deadline=None)
def test_hash_router_deterministic_and_in_range(n_exp, B, S):
    r = HashRouter(n_exp)
    toks = np.arange(B * S, dtype=np.int32).reshape(B, S)
    a = r.route_host(toks)
    b = r.route_host(toks)
    assert (a == b).all()
    assert ((0 <= a) & (a < n_exp)).all()


# ---------------------------------------------------------------- CoE
def test_coe_generate_groups_and_determinism(rng):
    cfg = reduced(get_config("samba-coe-expert-7b"))
    m = get_model(cfg)
    experts = []
    for i in range(3):
        p = m.init(jax.random.fold_in(rng, i))
        experts.append(jax.tree.map(np.asarray, p))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    coe = CompositionOfExperts(HashRouter(3), None, int(2.5 * nbytes))
    for i, h in enumerate(experts):
        coe.register(ExpertHandle(f"e{i}", cfg, h))
    toks = np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 8)).astype(np.int32)
    r1 = coe.generate(toks, 3)
    r2 = coe.generate(toks, 3)
    assert (r1.tokens == r2.tokens).all()
    assert r1.tokens.shape == (4, 3)
    # memory contract declared ahead of time (paper §V-B)
    c = coe.memory_contract("e0")
    assert c["hbm_bytes"] == nbytes


# ---------------------------------------------------------------- bw model
def test_bandwidth_model_reproduces_paper_trends():
    """Fig 12 / Table V trends: (1) SN40L-style capacity tier switches much
    faster than DGX host->GPU; (2) past-HBM expert counts spike DGX latency;
    (3) footprint: one capacity-tier node holds what needs many HBM-only
    nodes (Fig 13, 19x claim)."""
    seven_b = 7e9 * 2
    assert switch_cost(seven_b, DGX_A100) / switch_cost(seven_b, SN40L_NODE) > 25
    assert switch_cost(seven_b, DGX_H100) / switch_cost(seven_b, SN40L_NODE) > 12

    dc = decode_step_cost(7e9, 0, 8, DGX_A100)
    few = coe_latency(4, seven_b, 4, dc, 20, DGX_A100)     # all resident
    many = coe_latency(8, seven_b, 0, dc, 20, DGX_A100)    # all miss
    assert many["total_s"] > few["total_s"] * 2

    n_sn = footprint_nodes(850, seven_b, SN40L_NODE, use_capacity_tier=True)
    n_dgx = footprint_nodes(850, seven_b, DGX_A100, use_capacity_tier=False)
    assert n_sn == 1
    assert n_dgx >= 19


# ---------------------------------------------------------------- fusion
def test_fusion_plan_launch_ratio_matches_paper_range():
    """Fig 11: fused vs unfused kernel-call ratios land in the paper's
    observed 3x-30x band for decode. Decode HBM traffic is weight/cache
    bound so intensity barely moves (the paper's decode speedups come from
    launch overheads); prefill materializes activations unfused, so there
    fusion must raise intensity substantially (Table I regime)."""
    cfg = get_config("samba-coe-expert-7b")
    dec = model_fusion_report(cfg, batch=8, ctx=4096, seq=1)
    assert 3.0 < dec.launch_ratio < 30.0
    assert dec.traffic_ratio >= 1.0
    pre = model_fusion_report(cfg, batch=8, ctx=4096, seq=4096)
    assert pre.intensity_fused > pre.intensity_unfused * 1.5


def test_fusion_bytes_reduction():
    cfg = get_config("mixtral-8x7b")
    r = plan(cfg, batch=8, ctx=4096, seq=4096)
    assert r.fused_hbm_bytes < r.unfused_hbm_bytes
