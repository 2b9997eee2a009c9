"""Multi-socket RDU-node serving (ISSUE 5).

Three layers of coverage:

  * pure tests (topology validation, placement planning, pool pspecs) that
    run on any machine;
  * a subprocess acceptance test on 8 emulated CPU devices — part of the
    default tier-1 run, like ``tests/test_distributed.py`` — pinning the
    headline invariant: a TP=2 x 4-group node serves the single-device
    engine's request trace with logits matching it and the float32
    reference within a stated tolerance, no expert starves, and per-group
    HBM budgets are never exceeded;
  * in-process 8-device tests (the CI ``node-tests`` job runs the suite
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; they skip
    on fewer devices) covering the kv-replicated TP=8 path, least-loaded
    dispatch and online rebalancing.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.node.placement import (ExpertProfile, plan_expert_placement)
from repro.node.topology import make_node_topology

_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the node-tests CI job sets it)")


def _run_sub(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": os.environ["PATH"],
                            "HOME": os.environ.get("HOME", "/root"),
                            "JAX_PLATFORMS": "cpu"},
                       cwd=_ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


# ------------------------------------------------------------- topology
def test_topology_validation():
    with pytest.raises(ValueError):
        make_node_topology(0)
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        make_node_topology(4, 4, devices=jax.devices()[:1])
    topo = make_node_topology(1, 1, devices=jax.devices()[:1])
    assert topo.name == "1x1" and topo.n_sockets == 1
    assert topo.groups[0].mesh.axis_names == ("model",)


def test_machine_preset_follows_device_kind():
    """The node's tier preset comes from the chip JAX reports; an unknown
    accelerator is an error, never a silent v5e default."""
    from types import SimpleNamespace as NS
    from repro.core.memory_tiers import TPU_V5E_NODE, machine_for
    assert machine_for(jax.devices()) is TPU_V5E_NODE    # emulated sockets
    assert machine_for([NS(platform="tpu", device_kind="TPU v5 lite")]
                       ) is TPU_V5E_NODE
    with pytest.raises(KeyError, match="TPU v6 lite"):
        machine_for([NS(platform="tpu", device_kind="TPU v6 lite")])


@needs_8_devices
def test_topology_disjoint_device_groups():
    """Groups must partition the device list with no overlap."""
    topo = make_node_topology(2, 4)
    seen = [d for g in topo.groups for d in g.devices]
    assert len(seen) == 8 and len(set(seen)) == 8
    assert [g.tp for g in topo.groups] == [2, 2, 2, 2]
    assert make_node_topology(2).n_groups == 4     # default: fill the node


def test_paged_pool_pspec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config, pad_for_tp, reduced
    from repro.distributed.partitioning import paged_pool_pspec

    class FakeMesh:
        axis_names = ("model",)
        shape = {"model": 2}

    cfg = reduced(get_config("samba-coe-expert-7b"))    # kv=4: divisible
    assert paged_pool_pspec(cfg, FakeMesh()) == P(None, None, None, "model",
                                                  None)

    class FakeMesh8:
        axis_names = ("model",)
        shape = {"model": 8}

    cfg8 = pad_for_tp(cfg, 8)                           # kv=4 < 8: replicate
    assert paged_pool_pspec(cfg8, FakeMesh8()) == P(None, None, None, None,
                                                    None)


# ------------------------------------------------------------ placement
def _profiles(sizes, demands):
    return [ExpertProfile(f"e{i}", s, d)
            for i, (s, d) in enumerate(zip(sizes, demands))]


def test_placement_respects_group_budgets():
    sizes = [100] * 6
    pl = plan_expert_placement(_profiles(sizes, [1] * 6), [350, 350])
    for gid, names in pl.resident.items():
        assert len(names) * 100 <= 350     # never over a group's HBM share
    assert not pl.spilled                  # 3 + 3 fit
    assert all(pl.owners(f"e{i}") for i in range(6))
    # tighter budgets: the overflow spills instead of over-committing
    tight = plan_expert_placement(_profiles(sizes, [1] * 6), [250, 250])
    assert all(len(n) <= 2 for n in tight.resident.values())
    assert len(tight.spilled) == 2
    assert all(tight.owners(f"e{i}") for i in range(6))


def test_placement_balances_demand():
    """Two groups, skewed demand: the two hottest experts must land on
    different groups."""
    pl = plan_expert_placement(
        _profiles([100] * 4, [10, 10, 1, 1]), [200, 200])
    assert pl.owners("e0") != pl.owners("e1")


def test_placement_replicates_hot_expert():
    pl = plan_expert_placement(
        _profiles([100] * 3, [20, 1, 1]), [300, 300, 300],
        replicate_share=0.25)
    assert len(pl.owners("e0")) > 1          # >= 2 replicas of the hot one
    assert len(pl.owners("e1")) == 1


def test_placement_spills_when_nothing_fits():
    """An expert bigger than every group's HBM share streams from the
    shared store but still gets a dispatch owner."""
    pl = plan_expert_placement(
        _profiles([100, 1000], [1, 1]), [200, 200])
    assert "e1" in pl.spilled
    assert len(pl.owners("e1")) == 1
    assert all("e1" not in names for names in pl.resident.values())


def test_placement_uniform_fallback_without_demand():
    """Zero observed demand (cold start) plans uniform demand — experts
    spread across groups rather than piling onto group 0."""
    pl = plan_expert_placement(_profiles([100] * 4, [0] * 4), [200, 200])
    assert len(pl.resident[0]) == len(pl.resident[1]) == 2


# ------------------------------------------- acceptance test (subprocess)
@pytest.mark.slow
def test_node_2x4_matches_single_engine_bit_exact():
    """On 8 emulated CPU devices, a TP=2 x 4-group node serves the same
    trace as the single-device engine (mixed router-tagged and
    caller-tagged requests, same routing, same output lengths), and both
    engines' prefill and decode logits match the float32 reference and
    each other within ``BF16_TOL``; no expert starves, per-group HBM
    budgets hold at every step, and the paged pools leak nothing."""
    out = _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.core import CompositionOfExperts, ExpertHandle
        from repro.models import get_model
        from repro.serving import Request, ServingEngine
        from repro.node import make_node_topology, RDUNode

        class FirstTokenRouter:              # expert = first prompt token % n
            def __init__(self, n): self.n = n
            def route(self, params, tokens):
                return jnp.asarray(np.asarray(tokens)[:, 0] % self.n)

        cfg = reduced(get_config("samba-coe-expert-7b"))
        m = get_model(cfg)
        rng = jax.random.PRNGKey(0)
        n_exp = 4
        experts = [jax.tree.map(np.asarray,
                                m.init(jax.random.fold_in(rng, i)))
                   for i in range(n_exp)]
        nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))

        rs = np.random.RandomState(0)
        trace = []
        for i in range(12):                  # every expert gets traffic
            p = rs.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
            p[0] = p[0] - (p[0] % n_exp) + (i % n_exp)
            trace.append((i, p, 3 + i % 4, f"e{i % n_exp}" if i >= 10
                          else None))       # last two: caller-tagged

        coe = CompositionOfExperts(FirstTokenRouter(n_exp), None,
                                   int(10 * nbytes))
        for i, h in enumerate(experts):
            coe.register(ExpertHandle(f"e{i}", cfg, h))
        ref = ServingEngine(coe, cfg, max_len=24, n_slots=4, block_size=8)
        for rid, toks, n, tag in trace:
            ref.submit(Request(rid=rid, tokens=toks, max_new_tokens=n,
                               expert=tag))
        ref_done = {r.rid: (r.expert, r.output) for r in ref.drain()}
        assert len(ref_done) == len(trace)

        topo = make_node_topology(2, 4)
        node = RDUNode(topo, cfg, FirstTokenRouter(n_exp), None,
                       group_hbm_bytes=int(2.5 * nbytes),
                       group_kv_reserve_bytes=int(0.8 * nbytes),
                       n_slots=2, block_size=8, max_len=24)
        for i, h in enumerate(experts):
            node.register_expert(f"e{i}", h)
        for rid, toks, n, tag in trace:
            node.submit(Request(rid=rid, tokens=toks, max_new_tokens=n,
                                expert=tag))
        done = {}
        while node.has_work:
            for r in node.step():
                done[r.rid] = (r.expert, r.output)
            assert node.hbm_within_budget(), "HBM budget exceeded mid-run"
        assert len(done) == len(trace), "a request starved"
        served = {e for e, _ in done.values()}
        assert served == {f"e{i}" for i in range(n_exp)}, served
        for rid, (re, ro) in ref_done.items():
            ne, no = done[rid]
            assert re == ne, (rid, re, ne)
            assert len(ro) == len(no), rid
        # logits, not tokens: the TP programs sum in another order than the
        # single device, so a greedy token may flip on a near-tie. Both
        # paths must match the float32 reference, and each other at the
        # prefill, within the bf16 tolerance.
        from repro.models.reference import (BF16_TOL, assert_logits_close,
                                            reference_logits)
        from repro.serving.probe import probe_logits
        prompts = {}
        for rid, toks, n, tag in trace:
            prompts.setdefault(ref_done[rid][0], toks)
        assert sorted(prompts) == [f"e{i}" for i in range(n_exp)]
        for name, prompt in sorted(prompts.items()):
            lg1, fed1 = probe_logits(ref, coe.cache.activate(name), prompt)
            gs = node.groups[node.placement.owners(name)[0]]
            gp = gs.coe.cache.activate(name)
            lg2, fed2 = probe_logits(gs.engine, gp, prompt)
            for lg, fed in ((lg1, fed1), (lg2, fed2)):
                want = reference_logits(
                    cfg, gp, np.concatenate([prompt, fed]))[len(prompt) - 1:]
                assert_logits_close(lg, want, BF16_TOL, name)
            assert_logits_close(lg2[0], lg1[0], BF16_TOL, name + " prefill")
        for gs in node.groups:
            assert gs.engine.pool.stats.blocks_in_use == 0
            assert gs.coe.cache.used_bytes <= gs.coe.cache.capacity
            assert (gs.engine.pool.capacity_bytes()
                    <= gs.coe.hbm_budget.kv_bytes)
        st = node.stats()
        assert st.tokens_out == sum(n for _, _, n, _ in trace)
        node.close()
        print("NODE_BIT_EXACT_OK", st.tokens_out, round(st.imbalance, 3))
    """)
    assert "NODE_BIT_EXACT_OK" in out


@pytest.mark.slow
def test_node_disaggregated_matches_colocated_bit_exact():
    """ISSUE 8 acceptance: a node with one socket group dedicated to prefill
    (``prefill_groups=1``) and three decode groups produces greedy token
    streams identical to the colocated 4-group node for the same trace; the
    prefill-group -> decode-group paged-KV handoff never violates any
    group's HBM budget, both nodes' staging/decode pools leak nothing, and
    the AOT-warmed prefill buckets trigger zero post-warmup compilations.

    Groups are TP=1 here (the same shape ``--sweep-prefill``'s disagg axis
    gates): with one device per group the packed forward is placement-
    independent, so the comparison is bit-for-bit. TP>1 prefill groups run
    the same GSPMD path but a near-tie in the worker's first-token argmax
    can resolve differently under a different psum order, so cross-shape
    identity is only asserted at TP=1 (see docs/architecture.md)."""
    out = _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.core import CompositionOfExperts, ExpertHandle
        from repro.models import get_model
        from repro.serving import Request
        from repro.serving.prefill import compile_count
        from repro.node import make_node_topology, RDUNode

        class FirstTokenRouter:              # expert = first prompt token % n
            def __init__(self, n): self.n = n
            def route(self, params, tokens):
                return jnp.asarray(np.asarray(tokens)[:, 0] % self.n)

        cfg = reduced(get_config("samba-coe-expert-7b"))
        m = get_model(cfg)
        rng = jax.random.PRNGKey(0)
        n_exp = 3
        experts = [jax.tree.map(np.asarray,
                                m.init(jax.random.fold_in(rng, i)))
                   for i in range(n_exp)]
        nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))

        rs = np.random.RandomState(0)
        trace = []
        for i in range(10):                  # mixed prompt lengths 4..15
            S = 4 + rs.randint(0, 12)
            p = rs.randint(0, cfg.vocab_size, (S,)).astype(np.int32)
            p[0] = p[0] - (p[0] % n_exp) + (i % n_exp)
            trace.append((i, p, 3 + i % 4))

        def run(prefill_groups):
            node = RDUNode(make_node_topology(1, 4), cfg,
                           FirstTokenRouter(n_exp), None,
                           group_hbm_bytes=int(2.5 * nbytes),
                           group_kv_reserve_bytes=int(0.8 * nbytes),
                           n_slots=2, block_size=8, max_len=24,
                           prefill_groups=prefill_groups)
            for i, h in enumerate(experts):
                node.register_expert(f"e{i}", h)
            node.warmup()
            n_warm = compile_count()
            for rid, toks, n in trace:
                gid = node.submit(Request(rid=rid, tokens=toks,
                                          max_new_tokens=n))
                if prefill_groups:           # admits land on the worker
                    assert gid == 0, gid
            done, steps = {}, 0
            while node.has_work:
                for r in node.step():
                    done[r.rid] = (r.expert, r.output)
                assert node.hbm_within_budget(), "HBM budget exceeded"
                steps += 1
                assert steps < 10000
            assert len(done) == len(trace), "a request starved"
            for gs in node.groups:
                assert gs.engine.pool.stats.blocks_in_use == 0
            for w in node.workers:
                assert w.pool.stats.blocks_in_use == 0
            st = node.stats()
            compiles = compile_count() - n_warm
            node.close()
            return done, st, compiles

        co_done, co_st, co_compiles = run(prefill_groups=0)
        dis_done, dis_st, dis_compiles = run(prefill_groups=1)
        assert co_compiles == 0, f"colocated recompiled: {co_compiles}"
        assert dis_compiles == 0, f"disagg recompiled: {dis_compiles}"
        assert len(dis_st.prefill_groups) == 1
        assert len(co_st.prefill_groups) == 0
        for rid, (ce, co) in co_done.items():
            de, do = dis_done[rid]
            assert ce == de, (rid, ce, de)
            assert (co == do).all(), f"rid {rid}: {co} vs {do}"
        print("DISAGG_PARITY_OK", dis_st.tokens_out)
    """)
    assert "DISAGG_PARITY_OK" in out


# --------------------------------------------- in-process 8-device tests
@needs_8_devices
def test_tp8_single_group_matches_plain_engine():
    """The kv-replicated TP=8 path (GQA kv-heads < tp) matches the plain
    single-device engine on a padded config."""
    import jax.numpy as jnp
    from repro.configs import get_config, pad_for_tp, reduced
    from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
    from repro.models import get_model
    from repro.node import make_node_topology, RDUNode
    from repro.serving import Request, ServingEngine

    cfg = pad_for_tp(reduced(get_config("samba-coe-expert-7b")), 8)
    m = get_model(cfg)
    experts = [jax.tree.map(np.asarray, m.init(jax.random.PRNGKey(i)))
               for i in range(2)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    rs = np.random.RandomState(1)
    trace = [(i, rs.randint(0, cfg.vocab_size, (6,)).astype(np.int32), 3)
             for i in range(4)]

    coe = CompositionOfExperts(HashRouter(2), None, int(6 * nbytes))
    for i, h in enumerate(experts):
        coe.register(ExpertHandle(f"e{i}", cfg, h))
    ref = ServingEngine(coe, cfg, max_len=16, n_slots=2, block_size=8)
    for rid, toks, n in trace:
        ref.submit(Request(rid=rid, tokens=toks, max_new_tokens=n))
    ref_done = {r.rid: r.output for r in ref.drain()}

    node = RDUNode(make_node_topology(8, 1), cfg, HashRouter(2), None,
                   group_hbm_bytes=int(3 * nbytes),
                   group_kv_reserve_bytes=int(0.8 * nbytes),
                   n_slots=2, block_size=8, max_len=16)
    for i, h in enumerate(experts):
        node.register_expert(f"e{i}", h)
    runner = node.groups[0].engine.runner
    assert runner.tp == 8 and not runner.kv_sharded and runner.vocab_sharded
    for rid, toks, n in trace:
        node.submit(Request(rid=rid, tokens=toks, max_new_tokens=n))
    done = {r.rid: r.output for r in node.drain()}
    assert all((ref_done[r] == done[r]).all() for r in ref_done)
    node.close()


@needs_8_devices
def test_dispatch_least_loaded_and_rebalance():
    """Requests for one expert spread over its replica groups (least-loaded
    dispatch); rebalancing from observed demand replans and prewarms."""
    from repro.configs import get_config, reduced
    from repro.core import HashRouter
    from repro.models import get_model
    from repro.node import make_node_topology, RDUNode
    from repro.serving import Request

    cfg = reduced(get_config("samba-coe-expert-7b"))
    m = get_model(cfg)
    host = jax.tree.map(np.asarray, m.init(jax.random.PRNGKey(0)))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(host))
    node = RDUNode(make_node_topology(2, 4), cfg, HashRouter(2), None,
                   group_hbm_bytes=int(2.5 * nbytes),
                   group_kv_reserve_bytes=int(0.8 * nbytes),
                   n_slots=2, block_size=8, max_len=16,
                   replicate_share=0.25)
    node.register_expert("e0", host)
    node.register_expert("e1", jax.tree.map(np.copy, host))

    rs = np.random.RandomState(2)
    gids = [node.submit(Request(
        rid=i, tokens=rs.randint(0, cfg.vocab_size, (6,)).astype(np.int32),
        max_new_tokens=2, expert="e0")) for i in range(6)]
    owners = set(node.placement.owners("e0"))
    assert set(gids) <= owners
    if len(owners) > 1:                     # replicas exist: load spreads
        assert len(set(gids)) > 1
    node.drain()

    pl = node.rebalance()                   # e0 demand-heavy: replicated
    assert len(pl.owners("e0")) >= len(pl.owners("e1"))
    assert node.hbm_within_budget()
    st = node.stats()
    assert st.requests == 6 and st.tokens_out == 12
    assert sum(g["submitted"] for g in st.per_group) == 6
    node.close()
