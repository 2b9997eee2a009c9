"""Copy-on-write prefix sharing, session retention, and the refcounted
paged pool (ISSUE 9 tentpole).

Pool-level tests construct a tiny ``PagedKVCache`` directly (1 layer,
1 KV head, head_dim 2 — shapes are irrelevant to the bookkeeping under
test). Engine-level tests reuse the reduced samba-coe backbone and assert
the tentpole acceptance claims: byte-identical greedy streams shared vs
unshared, session turns adopting their history, zero leaked blocks and an
in-budget HBM accounting at every step of a drain.

Property tests run under ``hypothesis`` with no deadline: the first example
pays the jit compiles of the pool's device ops.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
from repro.models import get_model
from repro.serving import (PagedKVCache, PrefixIndex, Request, ServingEngine,
                           SessionManager)
from repro.serving.engine import _DeviceTableCache

B = 4  # block size for the pool-level tests


def mk_pool(n_blocks=16, scratch=False):
    return PagedKVCache(n_blocks, B, n_layers=1, kv_heads=1, head_dim=2,
                        dtype=jnp.float32, scratch=scratch)


def seat(pool, rid, tokens):
    """Open rid and commit len(tokens) positions whose K rows encode the
    token ids (so tests can check WHICH rows a table actually gathers)."""
    pool.open(rid)
    t = np.asarray(tokens, np.float32)
    k = t.reshape(1, -1, 1, 1) * np.ones((1, len(t), 1, 2), np.float32)
    pool.append(rid, jnp.asarray(k), jnp.asarray(k))


def rows(pool, rid):
    """Committed K rows of one rid as a flat int list (via gather)."""
    k, _ = pool.gather(rid)
    return [int(x) for x in np.asarray(k)[0, :, 0, 0]]


# ---------------------------------------------------------------- refcounts
def test_open_adopt_refcounts_and_free_ordering():
    pool = mk_pool()
    seat(pool, 0, range(10, 10 + 2 * B))          # two full blocks
    tbl = pool.table(0)
    assert [pool.refcount(b) for b in tbl] == [1, 1]

    pool.pin(tbl)                                  # match-window pin
    pool.open(1, adopt=tbl, adopt_len=2 * B)
    pool.unpin(tbl)
    assert [pool.refcount(b) for b in tbl] == [2, 2]
    assert pool.stats.shared_blocks == 2
    assert rows(pool, 1) == rows(pool, 0)          # same bytes, no copy

    pool.free(0)                                   # owner leaves first
    assert [pool.refcount(b) for b in tbl] == [1, 1]
    assert pool.stats.shared_blocks == 0
    assert rows(pool, 1) == list(range(10, 10 + 2 * B))
    pool.free(1)
    assert pool.stats.blocks_in_use == 0
    assert pool.free_blocks == pool.n_blocks


def test_adopt_validation():
    pool = mk_pool()
    seat(pool, 0, range(B))
    tbl = pool.table(0)
    with pytest.raises(ValueError):
        pool.open(1, adopt=tbl, adopt_len=0)       # empty adoption
    with pytest.raises(ValueError):
        pool.open(1, adopt=tbl, adopt_len=B + 1)   # beyond the blocks
    with pytest.raises(ValueError):
        pool.open(1, adopt=[7], adopt_len=2)       # block 7 is free
    pool.free(0)


def test_cow_split_preserves_sharers_bytes():
    """Writing into an adopted, partially-consumed shared tail block must
    split it: the writer gets a fresh copy, every other holder keeps the
    original rows byte-for-byte."""
    pool = mk_pool()
    seat(pool, 0, range(20, 20 + B + 2))           # one full + partial tail
    tbl = pool.table(0)
    pool.pin(tbl)
    pool.open(1, adopt=tbl, adopt_len=B + 2)       # adopt mid-block
    pool.unpin(tbl)
    assert pool.refcount(tbl[1]) == 2

    k = np.full((1, 1, 1, 2), 99.0, np.float32)
    pool.append(1, jnp.asarray(k), jnp.asarray(k))  # first write -> COW
    assert pool.stats.cow_splits == 1
    assert pool.table(1)[1] != tbl[1]              # tail swapped out
    assert pool.refcount(tbl[1]) == 1              # original back to owner
    assert rows(pool, 0) == list(range(20, 20 + B + 2))   # sharer untouched
    assert rows(pool, 1) == list(range(20, 20 + B + 2)) + [99]
    pool.free(0)
    pool.free(1)
    assert pool.stats.blocks_in_use == 0


def test_cow_skipped_when_tail_unshared_or_aligned():
    pool = mk_pool()
    seat(pool, 0, range(B + 1))
    pool.append(0, jnp.ones((1, 1, 1, 2)), jnp.ones((1, 1, 1, 2)))
    assert pool.stats.cow_splits == 0              # ref 1: write in place
    seat(pool, 1, range(30, 30 + B))               # block-aligned length
    tbl = pool.table(1)
    pool.pin(tbl)
    pool.open(2, adopt=tbl, adopt_len=B)
    pool.unpin(tbl)
    pool.append(2, jnp.ones((1, 1, 1, 2)), jnp.ones((1, 1, 1, 2)))
    assert pool.stats.cow_splits == 0              # tail full: new block
    assert pool.refcount(tbl[0]) == 2
    for r in (0, 1, 2):
        pool.free(r)
    assert pool.stats.blocks_in_use == 0


# ------------------------------------------------- free()/device-cache churn
def test_free_bumps_versions_before_block_reuse():
    """Regression: ``free`` must bump BOTH versions before its blocks hit
    the free list, so a ``_DeviceTableCache`` snapshot keyed on the old
    version can never serve a table whose blocks a later request reused."""
    pool = mk_pool(n_blocks=4, scratch=False)
    empty = np.zeros((4,), np.int32)
    cache = _DeviceTableCache(pool, max_blocks=4, empty_table=empty)

    seat(pool, 0, range(2 * B))
    t0 = np.asarray(cache.tables((0,)))
    v0 = pool.table_version
    pool.free(0)
    assert pool.table_version > v0 and pool.length_version > 0
    seat(pool, 1, range(40, 40 + 2 * B))           # reuses the freed blocks
    t1 = np.asarray(cache.tables((1,)))
    assert cache._tab_key[0] == pool.table_version     # fresh upload
    assert rows(pool, 1) == list(range(40, 40 + 2 * B))
    del t0, t1


def test_free_churn_many_rids_no_stale_reuse():
    """Interleaved open/free churn: every surviving rid still gathers its
    own rows (nobody reads a block that was recycled under them)."""
    pool = mk_pool(n_blocks=8)
    live = {}
    rid = 0
    rs = np.random.RandomState(3)
    for step in range(40):
        if live and (len(live) >= 3 or rs.rand() < 0.4):
            victim = int(rs.choice(list(live)))
            pool.free(victim)
            del live[victim]
        else:
            n = int(rs.randint(1, 2 * B))
            base = rid * 100
            seat(pool, rid, range(base, base + n))
            live[rid] = list(range(base, base + n))
            rid += 1
        for r, want in live.items():
            assert rows(pool, r) == want, f"rid {r} gathered foreign rows"
    for r in list(live):
        pool.free(r)
    assert pool.stats.blocks_in_use == 0
    assert pool.stats.allocs == pool.stats.frees


# ---------------------------------------------------------------- the index
def test_prefix_index_insert_match_roundtrip():
    pool = mk_pool()
    idx = PrefixIndex(pool)
    toks = np.arange(3 * B + 2, dtype=np.int32)
    seat(pool, 0, toks)
    assert idx.insert("e0", toks, pool.table(0)) == 3   # full blocks only
    pool.free(0)
    assert pool.stats.blocks_in_use == 3           # index keeps them alive

    m = idx.match("e0", toks)                      # same prompt again
    assert m is not None
    blocks, n = m
    assert n == 3 * B                              # every indexed full block
    assert len(blocks) == 3
    pool.unpin(blocks)

    m = idx.match("e0", toks[: 2 * B])             # exact-cover prompt:
    blocks, n = m                                  # capped so the suffix
    assert n == 2 * B - 1                          # forward has >=1 token
    assert len(blocks) == 2
    assert all(pool.refcount(b) >= 2 for b in blocks)   # pinned
    pool.unpin(blocks)

    assert idx.match("e1", toks) is None           # per-expert isolation
    assert idx.match("e0", toks + 1000) is None    # different tokens
    idx.clear()
    assert pool.stats.blocks_in_use == 0


def test_prefix_index_partial_tail_match():
    """A prompt sharing only part of an indexed block still adopts it —
    the rows are position-exact and the first write COW-splits."""
    pool = mk_pool()
    idx = PrefixIndex(pool)
    toks = np.arange(2 * B, dtype=np.int32)
    seat(pool, 0, toks)
    idx.insert("e0", toks, pool.table(0))
    pool.free(0)

    probe = np.concatenate([toks[: B + 2],
                            np.asarray([77, 78], np.int32)])
    m = idx.match("e0", probe)
    assert m is not None
    blocks, n = m
    assert n == B + 2                              # through the partial tail
    assert len(blocks) == 2
    pool.unpin(blocks)
    idx.clear()


def test_prefix_index_lru_leaf_reclaim():
    pool = mk_pool(n_blocks=4)
    idx = PrefixIndex(pool)
    pool.add_reclaimer(idx)
    for i in range(2):
        toks = np.arange(i * 50, i * 50 + 2 * B, dtype=np.int32)
        seat(pool, i, toks)
        idx.insert(f"e{i}", toks, pool.table(i))
        pool.free(i)
    assert pool.free_blocks == 0 and len(idx) == 4
    pool.open(9)                                   # needs fresh blocks
    pool.reserve(9, 2 * B)                         # forces a reclaim
    assert pool.length(9) == 0 and len(pool.table(9)) == 2
    assert len(idx) == 2                           # leaves (then roots) went
    pool.free(9)
    idx.clear()
    assert pool.stats.blocks_in_use == 0


# ------------------------------------------------------------------ sessions
def test_session_retain_adopt_evict():
    pool = mk_pool()
    sm = SessionManager(pool)
    toks = np.arange(2 * B + 1, dtype=np.int32)
    seat(pool, 0, toks)
    sm.retain("chat", 0, "e0", toks)
    assert "chat" in sm and pool.stats.blocks_in_use == 3

    nxt = np.concatenate([toks, np.asarray([5, 6], np.int32)])
    got = sm.adopt("chat", "e0", nxt)
    assert got is not None
    blocks, n = got
    assert n == len(toks)                          # whole history adopted
    assert "chat" not in sm                        # ownership handed over
    pool.open(1, adopt=blocks, adopt_len=n)
    pool.unpin(blocks)
    assert rows(pool, 1) == list(range(2 * B + 1))
    pool.free(1)
    assert pool.stats.blocks_in_use == 0

    seat(pool, 2, toks)
    sm.retain("chat", 2, "e0", toks)
    assert sm.adopt("chat", "e1", nxt) is None     # rerouted: KV useless
    assert "chat" not in sm and sm.evictions == 1
    assert pool.stats.blocks_in_use == 0


def test_session_cap_and_reclaim():
    pool = mk_pool(n_blocks=8)
    sm = SessionManager(pool, max_bytes=4 * pool._per_block_bytes())
    for i in range(3):
        seat(pool, i, np.arange(i * 30, i * 30 + 2 * B, dtype=np.int32))
        sm.retain(f"s{i}", i, "e0", np.arange(i * 30, i * 30 + 2 * B,
                                              dtype=np.int32))
    assert sm.bytes_retained() <= sm.max_bytes     # cap enforced on retain
    assert len(sm) == 2 and sm.evictions == 1
    freed = sm.reclaim(10)                         # pool-pressure path
    assert freed == 4 and len(sm) == 0
    assert pool.stats.blocks_in_use == 0


# --------------------------------------------------------- property tests
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=1, max_size=24),
       st.integers(1, 3))
def test_refcount_invariant_random_ops(ops, seed):
    """After ANY op sequence: every live block's refcounts sum to the table
    references + index references + outstanding pins, and no block is both
    referenced and on the free list."""
    pool = mk_pool(n_blocks=12)
    idx = PrefixIndex(pool)
    pool.add_reclaimer(idx)
    rs = np.random.RandomState(seed)
    rid = [0]
    live = []
    pins = []                                      # (blocks,) outstanding

    def check():
        index_refs = len(idx._entries)
        pin_refs = sum(len(p) for p in pins)
        assert (sum(pool._refs.values())
                == pool.live_table_refs() + index_refs + pin_refs)
        assert not (set(pool._refs) & set(pool._free))
        assert pool.stats.blocks_in_use == len(pool._refs)

    for op in ops:
        try:
            if op == 0:                            # open + append fresh
                n = int(rs.randint(1, 2 * B + 1))
                seat(pool, rid[0], rs.randint(0, 99, n))
                live.append(rid[0]); rid[0] += 1
            elif op == 1 and live:                 # free oldest
                pool.free(live.pop(0))
            elif op == 2 and live:                 # index a live rid
                r = live[int(rs.randint(len(live)))]
                toks = np.asarray(rows(pool, r), np.int32)
                idx.insert("e0", toks, pool.table(r))
            elif op == 3 and live:                 # match (leaves a pin)
                r = live[int(rs.randint(len(live)))]
                toks = np.asarray(rows(pool, r) + [1], np.int32)
                m = idx.match("e0", toks)
                if m is not None:
                    pins.append(m[0])
            elif op == 4 and pins:                 # adopt a pinned match
                blocks = pins.pop()
                n = (len(blocks) - 1) * B + 1
                pool.open(rid[0], adopt=blocks, adopt_len=n)
                pool.unpin(blocks)
                live.append(rid[0]); rid[0] += 1
            elif op == 5 and pins:                 # abandon a match
                pool.unpin(pins.pop())
        except MemoryError:
            pass                                   # pool exhausted: fine
        check()
    for p in pins:
        pool.unpin(p)
    for r in live:
        pool.free(r)
    idx.clear()
    check()
    assert pool.stats.blocks_in_use == 0


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3 * B - 1), st.integers(1, 6))
def test_cow_never_mutates_shared_rows(adopt_tokens, n_writes):
    """Whatever an adopter appends, every byte a sharer can gather stays
    exactly what it was before the adoption."""
    pool = mk_pool()
    total = 3 * B
    seat(pool, 0, range(100, 100 + total))
    before = rows(pool, 0)
    tbl = pool.table(0)[: -(-adopt_tokens // B)]
    pool.pin(tbl)
    pool.open(1, adopt=tbl, adopt_len=adopt_tokens)
    pool.unpin(tbl)
    for w in range(n_writes):
        k = np.full((1, 1, 1, 2), 500.0 + w, np.float32)
        pool.append(1, jnp.asarray(k), jnp.asarray(k))
    assert rows(pool, 0) == before
    assert rows(pool, 1)[:adopt_tokens] == before[:adopt_tokens]
    pool.free(0)
    pool.free(1)
    assert pool.stats.blocks_in_use == 0


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(1, 4))
def test_reclaim_never_frees_actively_referenced_block(n_sessions, seed):
    """Eviction under pressure (sessions then index) must only ever return
    blocks with NO remaining table/pin references to the free list."""
    pool = mk_pool(n_blocks=10)
    sm = SessionManager(pool, max_bytes=pool.capacity_bytes())
    idx = PrefixIndex(pool)
    pool.add_reclaimer(sm)
    pool.add_reclaimer(idx)
    rs = np.random.RandomState(seed)
    shared = np.arange(2 * B, dtype=np.int32)      # one common prefix
    active = None
    try:
        for i in range(n_sessions):
            seat(pool, i, shared)
            idx.insert("e0", shared, pool.table(i))
            sm.retain(f"s{i}", i, "e0", shared)
        m = idx.match("e0", np.concatenate(
            [shared, np.asarray([9], np.int32)]))
        if m is not None:
            pool.open(500, adopt=m[0], adopt_len=m[1])
            pool.unpin(m[0])
            active = 500
    except MemoryError:
        pass
    held = pool.table(active) if active is not None else []
    # drive hard pressure: ask for everything reclaimable and then some
    pool._reclaim(pool.n_blocks)
    for b in held:
        assert pool.refcount(b) >= 1, "reclaim freed an active block"
        assert b not in pool._free
    if active is not None:
        assert rows(pool, active) == [int(x) for x in shared[:pool.length(
            active)]]
        pool.free(active)
    sm.evict_all()
    idx.clear()
    assert pool.stats.blocks_in_use == 0


# ------------------------------------------------------------ engine level
@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("samba-coe-expert-7b"))


@pytest.fixture(scope="module")
def experts(cfg):
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    return [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
            for i in range(2)]


def _mk_coe(cfg, experts, **kw):
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    coe = CompositionOfExperts(HashRouter(len(experts)), None,
                               int(2.5 * nbytes), **kw)
    for i, h in enumerate(experts):
        coe.register(ExpertHandle(f"e{i}", cfg, h))
    return coe


def _session_trace(cfg, n_sessions=4, turns=2):
    rs = np.random.RandomState(11)
    sysp = rs.randint(1, cfg.vocab_size, (12,)).astype(np.int32)
    trace = []
    for s in range(n_sessions):
        trace.append({"sid": f"s{s}", "expert": f"e{s % 2}", "sys": sysp,
                      "user": [rs.randint(1, cfg.vocab_size, (5,))
                               .astype(np.int32) for _ in range(turns)]})
    return trace, turns


def _replay(eng, trace, turns, sharing):
    history = {}
    outs = {}
    for w in range(turns):
        rids = []
        for s in trace:
            p = np.concatenate([history.get(s["sid"], s["sys"]),
                                s["user"][w]])
            rid = w * 100 + int(s["sid"][1:])
            eng.submit(Request(rid=rid, tokens=p, max_new_tokens=3,
                               expert=s["expert"],
                               session_id=s["sid"] if sharing else None))
            rids.append((s, rid, p))
        done = {r.rid: r for r in eng.drain()}
        for s, rid, p in rids:
            outs[rid] = done[rid].output
            history[s["sid"]] = np.concatenate(
                [p, done[rid].output]).astype(np.int32)
    return outs


@pytest.mark.slow
def test_shared_vs_unshared_token_identity(cfg, experts):
    """The tentpole acceptance claim: prefix sharing changes where KV bytes
    live, never which tokens come out — and actually shares."""
    trace, turns = _session_trace(cfg)
    outs = {}
    for sharing in (False, True):
        coe = _mk_coe(cfg, experts)
        eng = ServingEngine(coe, cfg, max_len=64, n_slots=2, block_size=8,
                            prefix_sharing=sharing, kv_dtype=jnp.float32)
        outs[sharing] = _replay(eng, trace, turns, sharing)
        if sharing:
            assert eng.stats.prefix_hit_tokens > 0
            eng.release_shared()
            assert eng.pool.stats.blocks_in_use == 0
    assert outs[False].keys() == outs[True].keys()
    for rid in outs[False]:
        assert (outs[False][rid] == outs[True][rid]).all(), \
            f"rid {rid}: sharing changed the tokens"


@pytest.mark.slow
def test_session_resume_adopts_history(cfg, experts):
    """Turn 2 of a session must adopt turn 1's KV (history prefill skipped),
    second-turn hits covering at least the full first-turn sequence."""
    trace, turns = _session_trace(cfg, n_sessions=1, turns=2)
    coe = _mk_coe(cfg, experts)
    eng = ServingEngine(coe, cfg, max_len=64, n_slots=2, block_size=8,
                        prefix_sharing=True, kv_dtype=jnp.float32)
    s = trace[0]
    eng.submit(Request(rid=0, tokens=np.concatenate([s["sys"], s["user"][0]]),
                       max_new_tokens=3, expert=s["expert"],
                       session_id=s["sid"]))
    (r1,) = eng.drain()
    assert s["sid"] in eng.sessions
    turn1_len = len(r1.tokens) + len(r1.output)
    eng.submit(Request(
        rid=1, tokens=np.concatenate([r1.tokens, r1.output, s["user"][1]]),
        max_new_tokens=3, expert=s["expert"], session_id=s["sid"]))
    (r2,) = eng.drain()
    assert r2.prefix_hit_tokens >= turn1_len - 1   # -1: last KV not written
    eng.release_shared()
    assert eng.pool.stats.blocks_in_use == 0


@pytest.mark.slow
def test_drain_holds_hbm_budget_every_step(cfg, experts):
    """With a real carved HBM budget (weights vs KV reserve), a sharing
    drain must stay in budget at EVERY step and leak nothing."""
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    coe = _mk_coe(cfg, experts, kv_reserve_bytes=int(0.5 * nbytes))
    eng = ServingEngine(coe, cfg, max_len=64, n_slots=2, block_size=8,
                        prefix_sharing=True, kv_dtype=jnp.float32)
    trace, turns = _session_trace(cfg, n_sessions=3, turns=2)
    history = {}
    for w in range(turns):
        for s in trace:
            p = np.concatenate([history.get(s["sid"], s["sys"]),
                                s["user"][w]])
            eng.submit(Request(rid=w * 100 + int(s["sid"][1:]), tokens=p,
                               max_new_tokens=3, expert=s["expert"],
                               session_id=s["sid"]))
        pending = {w * 100 + int(s["sid"][1:]): s for s in trace}
        while pending:
            for r in eng.step():
                s = pending.pop(r.rid)
                history[s["sid"]] = np.concatenate(
                    [r.tokens, r.output]).astype(np.int32)
            assert eng.hbm_in_budget(), "HBM budget violated mid-drain"
    eng.release_shared()
    assert eng.pool.stats.blocks_in_use == 0
    assert eng.pool.stats.allocs == eng.pool.stats.frees
