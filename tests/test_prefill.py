"""AOT bucketed packed prefill (ISSUE 8).

Covers: bucket-selection and packing-plan properties (hypothesis), packing
never mixing tokens across segment boundaries, agreement of the packed
segment-masked forward with per-prompt sequential ``prefill_kv`` at f32
within a stated tolerance, engine-level
packed-vs-sequential drain parity, the zero-recompile-after-warmup
invariant on a mixed-length burst for both decode backends, and the
TTFT-histogram / bucket-counter observability series.
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
from repro.models.reference import F32_TOL, assert_logits_close
from repro.obs.metrics import MetricsRegistry
from repro.serving import (PackedPrefillRunner, Request, ServingEngine,
                           bucket_for, compile_count, compile_counts,
                           default_buckets, plan_packs,
                           reset_compile_counts)
from repro.serving.backends import PagedDecodeRunner

_CFG = None


def _cfg():
    """Lazy module-level config shared by the fixtures and the
    hypothesis-wrapped tests."""
    global _CFG
    if _CFG is None:
        _CFG = reduced(get_config("samba-coe-expert-7b"))
    return _CFG


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def experts(cfg):
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    return [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
            for i in range(2)]


@pytest.fixture(scope="module")
def params_f32(experts):
    return jax.tree.map(
        lambda x: np.asarray(x, np.float32)
        if x.dtype == jnp.bfloat16 else np.asarray(x), experts[0])


def _mk_coe(cfg, experts, capacity_experts=2.5):
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    coe = CompositionOfExperts(HashRouter(len(experts)), None,
                               int(capacity_experts * nbytes))
    for i, h in enumerate(experts):
        coe.register(ExpertHandle(f"e{i}", cfg, h))
    return coe


# ------------------------------------------------------- bucket selection
def test_default_buckets_powers_of_two():
    for m in (1, 15, 16, 17, 100, 4096):
        bks = default_buckets(m)
        assert bks[-1] >= m                    # covers max_len
        assert bks[0] == 16
        assert all(b == 2 * a for a, b in zip(bks, bks[1:]))
        # minimal: dropping the last bucket would uncover max_len
        assert len(bks) == 1 or bks[-2] < m
    with pytest.raises(ValueError):
        default_buckets(0)


@settings(max_examples=200)
@given(st.integers(min_value=1, max_value=4096))
def test_bucket_for_smallest_cover(n):
    """Every length maps to the SMALLEST bucket covering it."""
    buckets = default_buckets(4096)
    b = bucket_for(n, buckets)
    assert b >= n
    for x in buckets:
        if x < b:                              # every smaller bucket is
            assert x < n                       # too small for n
    with pytest.raises(ValueError):
        bucket_for(buckets[-1] + 1, buckets)


@settings(max_examples=100)
@given(st.lists(st.integers(min_value=1, max_value=64),
                min_size=1, max_size=30),
       st.integers(min_value=1, max_value=8))
def test_plan_packs_order_capacity_maximality(lengths, max_segments):
    buckets = default_buckets(64)
    packs = plan_packs(lengths, buckets, max_segments)
    flat = [i for p in packs for i in p]
    assert flat == list(range(len(lengths)))   # in order, nothing dropped
    for p in packs:
        assert 1 <= len(p) <= max_segments
        assert sum(lengths[i] for i in p) <= buckets[-1]
    # greedy maximality: a pack closes only because the next prompt would
    # overflow the largest bucket or the segment budget
    for p, q in zip(packs, packs[1:]):
        assert (len(p) == max_segments
                or sum(lengths[i] for i in p) + lengths[q[0]] > buckets[-1])


@settings(max_examples=25)
@given(st.lists(st.integers(min_value=1, max_value=20),
                min_size=1, max_size=4))
def test_pack_never_mixes_tokens_across_segments(lengths):
    """``pack`` gives every prompt its own contiguous span with its own
    segment id and per-segment restarting positions; padding carries a
    DISTINCT id (``max_segments``) so no pad token can attend (or be
    attended by) any real token."""
    runner = PackedPrefillRunner(_cfg(), buckets=default_buckets(128),
                                 max_segments=4)
    prompts = [np.full((n,), i + 1, np.int32) for i, n in enumerate(lengths)]
    toks, seg, pos, last, spans, bucket = runner.pack(prompts)
    assert bucket == bucket_for(sum(lengths), runner.buckets)
    off = 0
    for i, n in enumerate(lengths):
        assert spans[i] == (off, n)
        assert (toks[0, off:off + n] == i + 1).all()
        assert (seg[0, off:off + n] == i).all()
        assert (pos[0, off:off + n] == np.arange(n)).all()
        assert last[i] == off + n - 1
        off += n
    assert (seg[0, off:] == runner.max_segments).all()   # pad: own segment


# --------------------------------------------------- forward bit-equality
def test_packed_prefill_bit_equal_sequential_f32(cfg, params_f32):
    """Packed segment-masked forward == per-prompt sequential ``prefill_kv``
    at f32 within ``F32_TOL``: logits of every prompt's last token and the
    full per-prompt K/V slices. Masked cross-segment scores contribute exact
    zeros, so packing is not an approximation; the two are different XLA
    programs, so only their summation order may differ."""
    runner = PackedPrefillRunner(cfg, buckets=default_buckets(64),
                                 max_segments=4)
    seq = PagedDecodeRunner(cfg, scratch_row=0)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (7, 11, 5, 3)]
    res = runner(params_f32, prompts)
    assert res.bucket == 32                    # sum=26 -> bucket 32
    for i, p in enumerate(prompts):
        last, k, v = seq.prefill_kv(params_f32, jnp.asarray(p[None]))
        off, n = res.spans[i]
        assert_logits_close(res.logits[i], last, F32_TOL, f"prompt {i}")
        assert_logits_close(res.k[:, off:off + n], k, F32_TOL, f"{i}: K")
        assert_logits_close(res.v[:, off:off + n], v, F32_TOL, f"{i}: V")


# -------------------------------------------------- engine drain parity
def test_engine_packed_matches_sequential_drain(cfg, experts):
    """A mixed-length drain through ``prefill_mode='packed'`` produces the
    SAME token streams as ``prefill_mode='sequential'`` (bf16 engine
    default), and the packed engine emits the TTFT histogram and bucket
    counters."""
    rs = np.random.RandomState(7)
    lens = [3, 17, 9, 25, 5, 12, 7, 20, 4]
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]

    def run(mode):
        reg = MetricsRegistry()
        coe = _mk_coe(cfg, experts)
        eng = ServingEngine(coe, cfg, max_len=40, n_slots=3, block_size=8,
                            prefill_mode=mode, registry=reg)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, tokens=p, max_new_tokens=2 + i % 4))
        done = eng.drain()
        assert len(done) == len(prompts)
        assert eng.pool.stats.blocks_in_use == 0
        return {r.rid: r.output for r in done}, reg.snapshot(), done

    packed, snap, done = run("packed")
    sequential, seq_snap, _ = run("sequential")
    assert all((packed[i] == sequential[i]).all() for i in packed)
    # every request got exactly one TTFT observation, in both modes
    assert snap["serve.ttft_s:count"] == len(prompts)
    assert seq_snap["serve.ttft_s:count"] == len(prompts)
    assert snap["serve.ttft_s:p99"] >= snap["serve.ttft_s:p50"] > 0
    for r in done:                             # stamps ordered per request
        assert r.arrival_s <= r.prefill_done_s <= r.first_token_s
    # packed admission labels REAL buckets; counts sum to the request count
    packed_counts = {k: v for k, v in snap.items()
                     if k.startswith("serve.prefill_bucket")}
    assert sum(packed_counts.values()) == len(prompts)
    assert all(f"bucket={b}" in k for k in packed_counts
               for b in [int(k.split("bucket=")[1].rstrip("}"))]
               if b in default_buckets(40))


# -------------------------------------------- recompile regression gate
@pytest.mark.parametrize("backend", [
    "xla", pytest.param("fused", marks=pytest.mark.slow)])
def test_zero_recompiles_after_warmup_mixed_burst(cfg, experts, backend):
    """THE tentpole invariant: after ``warmup()`` a 200-request drain with
    adversarially mixed prompt lengths triggers ZERO new XLA compilations —
    every compile site in the serving path (packed prefill, pool scatter,
    sequential prefill, decode extend) reports through
    ``prefill.record_compile``, so a silent recompile cannot hide."""
    coe = _mk_coe(cfg, experts)
    eng = ServingEngine(coe, cfg, max_len=48, n_slots=4, block_size=8,
                        backend=backend)
    eng.warmup()
    reset_compile_counts()
    rs = np.random.RandomState(11)
    n = 200
    done = []
    for i in range(n):
        L = int(rs.randint(1, 37))             # 36 distinct lengths
        eng.submit(Request(
            rid=i, tokens=rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32),
            max_new_tokens=1 + int(rs.randint(0, 3))))
        if i % 5 == 4:                         # interleave decode + admits
            done.extend(eng.step())
    done.extend(eng.drain())
    assert len(done) == n
    assert eng.pool.stats.blocks_in_use == 0
    assert compile_count() == 0, (
        f"post-warmup XLA compilations detected: {compile_counts()}")


def test_sequential_mode_counts_recompiles(cfg, experts):
    """The control for the test above: the sequential path DOES recompile
    per novel prompt length — proving the counter hook actually observes
    the serving path rather than trivially reading zero."""
    coe = _mk_coe(cfg, experts)
    eng = ServingEngine(coe, cfg, max_len=32, n_slots=2, block_size=8,
                        prefill_mode="sequential")
    eng.warmup()
    reset_compile_counts()
    rs = np.random.RandomState(3)
    for i, L in enumerate((5, 9, 13)):         # three novel lengths
        eng.submit(Request(
            rid=i, tokens=rs.randint(0, cfg.vocab_size, (L,)).astype(np.int32),
            max_new_tokens=2))
    eng.drain()
    assert compile_count("prefill_kv") == 3
