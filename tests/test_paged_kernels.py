"""Paged-native Pallas fused decode vs the XLA reference (ISSUE 7).

Covers, bottom-up:
  * ``decode_paged`` against a dense masked-softmax oracle across GQA
    ratios, ragged lengths straddling block boundaries, and minimal/full
    lanes;
  * the ``qkv_rope_paged`` prologue and ``oproj_ffn_swiglu`` epilogue
    against the model-layer reference math;
  * ``fused_paged_extend`` vs ``xla_paged_extend`` (fp tolerance) across
    GQA variants, with an inactive lane scattering to scratch;
  * the backend seam itself (selection, validation, unsupported configs);
  * end-to-end engine drains: at f32, greedy token streams must be
    IDENTICAL across backends (the acceptance claim), speculative decode
    must match too (its emitted tokens come from the g>1 verify step, which
    is the XLA body under both backends), and the device-side table cache
    must reuse arrays across rounds;
  * a TP=2 subprocess drain (node/execution.py fused shard_map path).

Precision contract: strict token identity holds at f32. In bf16 the XLA
body rounds every op boundary to bf16 while the fused kernels keep f32 in
VMEM, so bf16 gets tolerance-level parity only (the extend test covers it).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
from repro.models import get_model
from repro.models import layers as L
from repro.serving import (FusedPagedBackend, Request, ServingEngine,
                           SpeculativeDecode, XlaPagedBackend, make_backend,
                           make_runner)
from repro.serving.backends import (fused_kernel_hbm_bytes,
                                    fused_paged_extend, xla_paged_extend)


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("samba-coe-expert-7b"))


def _f32(tree):
    """Param trees init as bf16 regardless of cfg.dtype — cast for the
    strict-parity contract."""
    return jax.tree.map(
        lambda x: np.asarray(x, np.float32)
        if x.dtype == jnp.bfloat16 else np.asarray(x), tree)


def _gqa_cfg(cfg, n_kv):
    return dataclasses.replace(cfg, n_kv_heads=n_kv)


# ------------------------------------------------------ decode_paged oracle
def _paged_attention_ref(q, kp, vp, tables, len1):
    """Dense gather + masked softmax — the oracle decode_paged must match."""
    B, Hq, dh = q.shape
    Hkv = kp.shape[2]
    G = Hq // Hkv
    maxb, block = tables.shape[1], kp.shape[1]
    S = maxb * block
    kc = kp[tables].reshape(B, S, Hkv, dh)
    vc = vp[tables].reshape(B, S, Hkv, dh)
    qg = q.reshape(B, Hkv, G, dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kc,
                   preferred_element_type=jnp.float32) / np.sqrt(dh)
    mask = jnp.arange(S)[None, None, None, :] < len1[:, None, None, None]
    pa = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", pa, vc,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, dh)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (4, 1)])
def test_decode_paged_matches_dense_reference(hq, hkv):
    """GQA ratios 1/2/4, ragged lengths straddling block boundaries, block
    tables in scrambled pool order."""
    from repro.kernels.flash_attention.ops import decode_paged

    B, dh, block, maxb = 4, 32, 8, 3
    rows = B * maxb + 1                       # + scratch
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.standard_normal((B, hq, dh)), jnp.float32)
    kp = jnp.asarray(rs.standard_normal((rows, block, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rs.standard_normal((rows, block, hkv, dh)), jnp.float32)
    perm = rs.permutation(rows - 1)           # scrambled block placement
    tables = jnp.asarray(perm[:B * maxb].reshape(B, maxb), jnp.int32)
    # 1 token, mid-block, exactly one block, straddling into block 2
    len1 = jnp.asarray([1, 5, 8, 17], jnp.int32)

    got = decode_paged(q, kp, vp, tables, len1, interpret=True)
    ref = _paged_attention_ref(q, kp, vp, tables, len1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_paged_minimal_and_full_lanes_finite():
    """len1=1 (single cached token) through len1=S (every block full) stay
    finite and correct — the inactive-lane story relies on garbage lanes
    producing finite output the caller ignores."""
    from repro.kernels.flash_attention.ops import decode_paged

    B, hq, hkv, dh, block, maxb = 4, 4, 2, 32, 8, 2
    S = maxb * block
    rows = B * maxb + 1
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.standard_normal((B, hq, dh)), jnp.float32)
    kp = jnp.asarray(rs.standard_normal((rows, block, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rs.standard_normal((rows, block, hkv, dh)), jnp.float32)
    tables = jnp.arange(B * maxb, dtype=jnp.int32).reshape(B, maxb)
    len1 = jnp.asarray([1, S, S // 2, 1], jnp.int32)
    got = decode_paged(q, kp, vp, tables, len1, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    ref = _paged_attention_ref(q, kp, vp, tables, len1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- prologue/epilogue kernels
def test_qkv_rope_paged_matches_layer_reference(cfg):
    """RMSNorm + QKV + per-lane RoPE == models.layers math (f32), including
    ragged per-lane positions (no shared position scalar in paged decode)."""
    from repro.kernels.fused_decode.kernel import qkv_rope_paged

    c = _gqa_cfg(cfg, 2)
    B, D, dh = 4, c.d_model, c.head_dim
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.standard_normal((B, D)), jnp.float32)
    scale = jnp.asarray(rs.standard_normal((D,)), jnp.float32)
    wq = jnp.asarray(rs.standard_normal((D, c.n_heads, dh)) * 0.05,
                     jnp.float32)
    wk = jnp.asarray(rs.standard_normal((D, c.n_kv_heads, dh)) * 0.05,
                     jnp.float32)
    wv = jnp.asarray(rs.standard_normal((D, c.n_kv_heads, dh)) * 0.05,
                     jnp.float32)
    pos = jnp.asarray([0, 3, 17, 100], jnp.int32)

    q, k, v = qkv_rope_paged(x, scale, wq, wk, wv, pos,
                             theta=c.rope_theta, interpret=True)

    xn = L.rms_norm(x, scale)
    q_ref = L.apply_rope(c, jnp.einsum("bd,dhk->bhk", xn, wq)[:, None],
                         pos[:, None])[:, 0]
    k_ref = L.apply_rope(c, jnp.einsum("bd,dhk->bhk", xn, wk)[:, None],
                         pos[:, None])[:, 0]
    v_ref = jnp.einsum("bd,dhk->bhk", xn, wv)
    for got, ref in ((q, q_ref), (k, k_ref), (v, v_ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wide", [False, True])
def test_oproj_ffn_swiglu_matches_reference(cfg, wide):
    """Whole layer epilogue (out-proj + residual + norm + SwiGLU + residual)
    against the explicit composition, with a non-default block_f so the
    FFN grid actually iterates. ``wide`` makes d_model large enough that Wo
    streams in several row tiles, as at published widths."""
    from repro.kernels.fused_decode.kernel import oproj_ffn_swiglu

    B, D, F, HD = 4, cfg.d_model, cfg.d_ff, cfg.n_heads * cfg.head_dim
    if wide:
        D, HD = 2048, 256                    # f32 Wo tiles of 128 rows
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.standard_normal((B, D)), jnp.float32)
    attn = jnp.asarray(rs.standard_normal((B, HD)), jnp.float32)
    wo = jnp.asarray(rs.standard_normal((HD, D)) * 0.05, jnp.float32)
    scale = jnp.asarray(rs.standard_normal((D,)), jnp.float32)
    wg = jnp.asarray(rs.standard_normal((D, F)) * 0.05, jnp.float32)
    wu = jnp.asarray(rs.standard_normal((D, F)) * 0.05, jnp.float32)
    wd = jnp.asarray(rs.standard_normal((F, D)) * 0.05, jnp.float32)

    got = oproj_ffn_swiglu(x, attn, wo, scale, wg, wu, wd, block_f=64,
                           interpret=True)
    y = x + attn @ wo
    yn = L.rms_norm(y, scale)
    g, u = yn @ wg, yn @ wu
    ref = y + (g * jax.nn.sigmoid(g) * u) @ wd
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ffn_swiglu_partial_form_composes_with_residual(cfg):
    """residual=False (the TP partial the fused shard_map path psums) plus
    the residual add equals the residual=True kernel."""
    from repro.kernels.fused_decode.kernel import ffn_swiglu

    B, D, F = 4, cfg.d_model, cfg.d_ff
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.standard_normal((B, D)), jnp.float32)
    scale = jnp.asarray(rs.standard_normal((D,)), jnp.float32)
    wg = jnp.asarray(rs.standard_normal((D, F)) * 0.05, jnp.float32)
    wu = jnp.asarray(rs.standard_normal((D, F)) * 0.05, jnp.float32)
    wd = jnp.asarray(rs.standard_normal((F, D)) * 0.05, jnp.float32)
    full = ffn_swiglu(x, scale, wg, wu, wd, block_f=64, interpret=True)
    part = ffn_swiglu(x, scale, wg, wu, wd, block_f=64, residual=False,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(x + part),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------- fused vs XLA extend
@pytest.mark.parametrize("n_kv", [1, 2, 4])
def test_fused_extend_matches_xla_extend(cfg, n_kv):
    """One fused step == one XLA step (fp tolerance at f32): logits AND the
    scattered pool state, across GQA variants, with an inactive lane (must
    scatter to scratch under both backends) and ragged lengths straddling a
    block boundary."""
    c = _gqa_cfg(cfg, n_kv)
    params = _f32(get_model(c).init(jax.random.PRNGKey(5)))
    B, block, maxb = 4, 8, 3
    rows = B * maxb + 1
    scratch = rows - 1
    rs = np.random.RandomState(6)
    shape = (c.n_layers, rows, block, n_kv, c.head_dim)
    pk = jnp.asarray(rs.standard_normal(shape) * 0.1, jnp.float32)
    pv = jnp.asarray(rs.standard_normal(shape) * 0.1, jnp.float32)
    tables = jnp.asarray(
        rs.permutation(rows - 1)[:B * maxb].reshape(B, maxb), jnp.int32)
    lengths = jnp.asarray([0, 7, 8, 15], jnp.int32)   # ragged + straddling
    active = jnp.asarray([True, True, False, True])
    tokens = jnp.asarray(rs.randint(0, c.vocab_size, (B, 1)), jnp.int32)

    lg_x, pk_x, pv_x = xla_paged_extend(c, params, pk, pv, tables, lengths,
                                        active, tokens, scratch)
    lg_f, pk_f, pv_f = fused_paged_extend(c, params, pk, pv, tables, lengths,
                                          active, tokens, scratch,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_x),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pk_f), np.asarray(pk_x),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pv_f), np.asarray(pv_x),
                               rtol=2e-4, atol=2e-4)
    # the inactive lane's own blocks are untouched; only scratch absorbed it
    lane = 2
    for row in np.asarray(tables)[lane]:
        np.testing.assert_array_equal(np.asarray(pk_f[:, row]),
                                      np.asarray(pk[:, row]))


# ----------------------------------------------------------- backend seam
def test_backend_seam_selection_and_validation(cfg):
    runner = make_runner(cfg, scratch_row=7, backend="fused")
    assert runner.backend_name == "fused"
    assert isinstance(runner.backend, FusedPagedBackend)
    assert isinstance(make_runner(cfg, 7).backend, XlaPagedBackend)
    # instance passthrough
    be = FusedPagedBackend(cfg, 7, interpret=True)
    assert make_backend(be, cfg, 7) is be
    with pytest.raises(ValueError, match="unknown backend"):
        make_runner(cfg, 7, backend="dataflow")
    # g>1 under the fused backend falls back to the XLA body (speculative
    # verify) — both callables must exist
    assert be.extend_fn(4, 1) is not None and be.extend_fn(4, 3) is not None
    assert be.kernel_hbm_bytes(4, 3, 8) == fused_kernel_hbm_bytes(
        cfg, 4, 3, 8)


def test_fused_backend_rejects_unsupported_families(cfg):
    for bad in (dataclasses.replace(cfg, qkv_bias=True),
                dataclasses.replace(cfg, act="gelu"),
                dataclasses.replace(cfg, norm="layer")):
        with pytest.raises(ValueError, match="backend='xla'"):
            FusedPagedBackend(bad, 0)
    # the seam surfaces the same error through the engine constructor


# ------------------------------------------------- engine drains (f32)
def _mk_coe_f32(cfg, n_experts=2):
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    experts = [_f32(m.init(jax.random.fold_in(rng, i)))
               for i in range(n_experts)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    coe = CompositionOfExperts(HashRouter(n_experts), None, int(5 * nbytes))
    for i, h in enumerate(experts):
        coe.register(ExpertHandle(f"e{i}", cfg, h))
    return coe


def _drain(cfg, backend, policy=None, n=6):
    """Fresh engine + fixed request trace -> {rid: tokens}. f32 weights and
    f32 KV: the regime where fused and xla are token-identical."""
    coe = _mk_coe_f32(cfg)
    eng = ServingEngine(coe, cfg, max_len=32, n_slots=3, block_size=8,
                        backend=backend, kv_dtype=jnp.float32,
                        policy=policy() if policy else None)
    rs = np.random.RandomState(7)
    for i in range(n):
        # ragged prompts so lengths straddle block boundaries mid-drain
        eng.submit(Request(rid=i, tokens=rs.randint(
            0, cfg.vocab_size, (5 + 3 * (i % 3),)).astype(np.int32),
            max_new_tokens=3 + i % 4))
    done = eng.drain()
    assert eng.pool.stats.blocks_in_use == 0
    return {r.rid: r.output for r in done}, eng


def test_greedy_drain_token_identical_across_backends(cfg):
    """The acceptance claim: at f32, fused and xla greedy token streams are
    byte-identical, request for request."""
    xla, _ = _drain(cfg, "xla")
    fused, eng = _drain(cfg, "fused")
    assert xla.keys() == fused.keys()
    for rid in xla:
        np.testing.assert_array_equal(xla[rid], fused[rid]), rid
    assert eng.runner.backend_name == "fused"


def test_speculative_drain_identical_across_backends(cfg):
    """Speculative emitted tokens come from the g>1 verify step — the XLA
    body under BOTH backends — so the streams match; the fused backend only
    accelerates the single-token draft loop."""
    d_cfg = dataclasses.replace(cfg, n_layers=2)

    def policy():
        d_host = _f32(get_model(d_cfg).init(jax.random.PRNGKey(9)))
        return SpeculativeDecode(d_cfg, d_host, gamma=3)

    xla, _ = _drain(cfg, "xla", policy=policy, n=4)
    fused, eng = _drain(cfg, "fused", policy=policy, n=4)
    for rid in xla:
        np.testing.assert_array_equal(xla[rid], fused[rid]), rid
    # the draft runner inherited the engine's backend through the seam
    assert eng.policy.d_runner.backend_name == "fused"


def test_device_table_cache_reuses_arrays(cfg):
    """Satellite (b): per-round host->device uploads are cached behind the
    pool's version counters — identical slot state yields the SAME device
    arrays, and mutation bumps the version."""
    coe = _mk_coe_f32(cfg)
    eng = ServingEngine(coe, cfg, max_len=32, n_slots=2, block_size=8)
    eng.submit(Request(rid=0, tokens=np.arange(6, dtype=np.int32),
                       max_new_tokens=4))
    eng.step()
    t1, l1 = eng._device_tables()
    t2, l2 = eng._device_tables()
    assert t1 is t2 and l1 is l2
    v_tab, v_len = eng.pool.table_version, eng.pool.length_version
    eng.step()                       # advances lengths (maybe allocs blocks)
    assert eng.pool.length_version > v_len
    t3, l3 = eng._device_tables()
    assert l3 is not l1
    if eng.pool.table_version == v_tab:      # no new block this round
        assert t3 is t1                      # table upload skipped entirely
    act = np.array([True, False])
    a1 = eng._device_active(act)
    a2 = eng._device_active(act.copy())
    assert a1 is a2
    eng.drain()
    assert eng.pool.stats.blocks_in_use == 0


# --------------------------------------------------- TP fused path (TP=2)
def test_tp2_fused_drain_matches_xla(cfg):
    """node/execution.py shard_map fused path: TP=2 greedy drains are
    token-identical to the TP=2 XLA backend at f32 (subprocess so the
    emulated 2-device env is set before jax imports)."""
    import os
    import pathlib
    import subprocess
    import sys
    import textwrap

    root = str(pathlib.Path(__file__).resolve().parent.parent)
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced, pad_for_tp
        from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
        from repro.launch.mesh import make_device_mesh
        from repro.models import get_model
        from repro.node.execution import make_group_engine
        from repro.serving import Request

        cfg = pad_for_tp(reduced(get_config("samba-coe-expert-7b")), 2)
        f32 = lambda t: jax.tree.map(
            lambda x: np.asarray(x, np.float32)
            if x.dtype == jnp.bfloat16 else np.asarray(x), t)
        experts = [f32(get_model(cfg).init(jax.random.PRNGKey(i)))
                   for i in range(2)]
        nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
        mesh = make_device_mesh((2,), ("model",), jax.devices()[:2])

        def drain(backend):
            coe = CompositionOfExperts(HashRouter(2), None, int(5 * nbytes))
            for i, h in enumerate(experts):
                coe.register(ExpertHandle(f"e{i}", cfg, h))
            eng = make_group_engine(coe, cfg, mesh, max_len=32, n_slots=2,
                                    block_size=8, backend=backend,
                                    kv_dtype=jnp.float32)
            assert eng.runner.backend_name == backend
            rs = np.random.RandomState(11)
            for i in range(4):
                eng.submit(Request(rid=i, tokens=rs.randint(
                    0, cfg.vocab_size, (5 + 2 * (i % 2),)).astype(np.int32),
                    max_new_tokens=3 + i % 3))
            done = {r.rid: r.output for r in eng.drain()}
            assert eng.pool.stats.blocks_in_use == 0
            return done

        xla, fused = drain("xla"), drain("fused")
        assert xla.keys() == fused.keys()
        for rid in xla:
            assert (xla[rid] == fused[rid]).all(), rid
        print("TP2_PARITY_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": os.environ["PATH"],
                            "HOME": os.environ.get("HOME", "/root"),
                            "JAX_PLATFORMS": "cpu"},
                       cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "TP2_PARITY_OK" in r.stdout
