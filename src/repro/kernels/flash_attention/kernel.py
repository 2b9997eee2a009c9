"""Flash-attention Pallas kernels (prefill + decode).

TPU adaptation notes (vs the paper's spatially-fused RDU pipeline):
  * Online-softmax streaming over KV blocks — KV tiles stream HBM->VMEM, the
    running (m, l, acc) state lives in VMEM (the RDU's PMU stage buffers).
  * Causal/SWA block skipping: the kv loop bound is computed from the grid
    position, so masked-out tiles are never fetched or computed — the same
    useful-FLOPs-only property as the model-level ``block_attention``.
  * Block shapes are (128, head_dim)-aligned for the MXU.

``flash_prefill``: grid (B, Hq, nq). KV for the matching kv-head is resident;
the fori loop streams kv blocks with masking only on the diagonal block.
``flash_decode``:  grid (B, ns) with VMEM scratch accumulators carried across
the sequential last grid axis; masked by runtime ``length``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# ----------------------------------------------------------------------
# Prefill
# ----------------------------------------------------------------------

def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, causal, window, scale):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, dh)
    S = k_ref.shape[2]
    nk = S // bk
    q_start = iq * bq

    if causal:
        hi = jax.lax.div(q_start + bq + bk - 1, bk)
        hi = jnp.minimum(hi, nk)
    else:
        hi = nk
    if window:
        lo = jnp.maximum((q_start - window + 1) // bk, 0)
    else:
        lo = 0

    def body(j, carry):
        acc, m, l = carry
        k = jax.lax.dynamic_slice_in_dim(k_ref[0, 0], j * bk, bk, 0)
        v = jax.lax.dynamic_slice_in_dim(v_ref[0, 0], j * bk, bk, 0)
        s = jnp.dot(q, k.astype(jnp.float32).T)            # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha[:, None] + jnp.dot(p.astype(v.dtype), v,
                                             preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    dh = q_ref.shape[-1]
    acc0 = jnp.zeros((bq, dh), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, causal=True, window=0, block_q=128, block_k=128,
                  interpret=False):
    """q (B,Hq,S,dh), k/v (B,Hkv,S,dh) -> (B,Hq,S,dh)."""
    B, Hq, S, dh = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0
    grid = (B, Hq, S // bq)
    kernel = functools.partial(_prefill_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, scale=1.0 / math.sqrt(dh))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, dh), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, S, dh), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_prefill",
    )(q, k, v)


# ----------------------------------------------------------------------
# Decode
# ----------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, bk, scale):
    j = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale               # (Hq, dh)
    k = k_ref[0, :, 0]                                     # (bk, dh) one kv head
    v = v_ref[0, :, 0]
    Hq = q.shape[0]
    s = jnp.dot(q, k.astype(jnp.float32).T)                # (Hq, bk)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (Hq, bk), 1)
    mask = kpos < length
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, s.max(-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_old - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == ns - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def _decode_paged_kernel(tables_ref, len1_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, block, scale):
    """One lane streams its block table sequentially over the innermost grid
    axis; (m, l, acc) online-softmax state persists in VMEM across the
    blocks (same scheme as ``_decode_kernel``, but the KV tile for step
    ``j`` is pool row ``tables[b, j]`` — gathered by the BlockSpec index map
    off the scalar-prefetched table, never materialized contiguously).

    The tile holds every kv head of the pool block, so its minor dims are
    the pool's own ``(Hkv, dh)``. Scores are lane reductions of q * k on
    the vector unit: the (block, Hkv) layout never has to be transposed or
    split per head, whatever Hkv is."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len1_ref[b]

    # blocks past the lane's length are fully masked: skip the math (tables
    # are padded with the scratch row, so their fetch is always in-bounds,
    # and repeated padding indices elide the DMA)
    @pl.when(j * block < length)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale           # (G, Hkv, dh)
        k = k_ref[0].astype(jnp.float32)                   # (block, Hkv, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.sum(q[:, None] * k[None], axis=-1,
                    keepdims=True)                         # (G, block, Hkv, 1)
        kpos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        s = jnp.where(mask, s, NEG_INF)
        m_old = m_ref[...]                                 # (G, Hkv, 1)
        m_new = jnp.maximum(m_old, s.max(axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v[None], axis=1)
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode_paged(q, k_pool, v_pool, tables, len1, *, interpret=False):
    """Paged-native flash decode: gather K/V straight from the block pool.

    q       (B, G, Hkv, dh)  — GQA-grouped queries (G = Hq // Hkv), group
            index major so each tile's kv heads line up with q's
    pools   (rows, block, Hkv, dh) — the ``PagedKVCache`` k/v arrays
    tables  (B, maxb) int32  — per-lane block tables, padded with the pool's
            scratch row (every entry must be a valid row index)
    len1    (B,) int32       — valid cache positions per lane, INCLUSIVE of
            the token scattered this step (lengths + 1 for live lanes; >= 1
            always — empty/inactive lanes attend their padding rows and
            produce finite garbage the caller ignores, exactly like the XLA
            paged-extend reference)
    Returns (B, G, Hkv, dh).

    ``tables``/``len1`` ride the scalar-prefetch channel
    (``PrefetchScalarGridSpec``) so the KV BlockSpec index map resolves
    ``tables[b, j]`` BEFORE the tile DMA is issued — the vLLM-style
    block-sparse gather, expressed as a data-dependent index map. One grid
    step fetches a whole pool block (all kv heads), so the tile's last two
    dims are the pool's ``(Hkv, dh)`` at any ``block``.
    """
    B, G, Hkv, dh = q.shape
    rows, block, Hkv_p, _ = k_pool.shape
    assert Hkv_p == Hkv, (Hkv_p, Hkv)
    maxb = tables.shape[1]
    kernel = functools.partial(_decode_paged_kernel, block=block,
                               scale=1.0 / math.sqrt(dh))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, maxb),
        in_specs=[
            pl.BlockSpec((1, G, Hkv, dh), lambda b, j, tr, lr: (b, 0, 0, 0)),
            pl.BlockSpec((1, block, Hkv, dh),
                         lambda b, j, tr, lr: (tr[b, j], 0, 0, 0)),
            pl.BlockSpec((1, block, Hkv, dh),
                         lambda b, j, tr, lr: (tr[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Hkv, dh),
                               lambda b, j, tr, lr: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Hkv, dh), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, Hkv, dh), q.dtype),
        interpret=interpret,
        name="decode_paged",
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(len1, jnp.int32),
      q, k_pool, v_pool)


def flash_decode(q, k_cache, v_cache, length, *, block_k=512, interpret=False):
    """q (B,Hq,dh); caches (B,S,Hkv,dh); length (1,) int32 -> (B,Hq,dh).

    One kv-head variant per call keeps blocks MXU-aligned; GQA is handled by
    the ops wrapper (vmap over kv heads with the matching q-head group).
    """
    B, Hq, dh = q.shape
    _, S, Hkv, _ = k_cache.shape
    assert Hkv == 1, "ops wrapper splits kv heads"
    bk = min(block_k, S)
    assert S % bk == 0
    grid = (B, S // bk)
    kernel = functools.partial(_decode_kernel, bk=bk, scale=1.0 / math.sqrt(dh))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, j: (0,)),
            pl.BlockSpec((1, Hq, dh), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, dh), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Hq, dh), jnp.float32),
            pltpu.VMEM((Hq,), jnp.float32),
            pltpu.VMEM((Hq,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(length, q, k_cache, v_cache)
