"""Fused decode-step Pallas kernels: the paper's headline dataflow claim.

SN40L fuses an *entire decoder layer* into one kernel for autoregressive
decode (paper §VI-B: >85% HBM bandwidth, near-zero launch overhead). On TPU
the equivalent is a minimal-HBM-traffic schedule: every weight byte is read
exactly once per token, and all intermediate activations stay in VMEM.

Kernels:
  * ``qkv_rope``:  RMSNorm + QKV projection + RoPE in one pass. Grid streams
    one head-column block of the fused [Wq|Wk|Wv] matrix per step; the
    normalized activation vector lives in VMEM, rotary phases are computed
    in-kernel from the position scalar. V-heads skip rotation by flag.
  * ``ffn_swiglu``: RMSNorm + SwiGLU MLP + residual for decode. Grid streams
    (gate, up, down) column/row blocks; the f32 output accumulator persists
    in VMEM scratch across the sequential grid axis — one pass over all FFN
    weights, the theoretical HBM minimum.

The attention itself is ``kernels/flash_attention.flash_decode`` (cache
streaming at HBM bandwidth).

Paged-native variants (what ``serving.backends.FusedPagedBackend`` runs —
these take the engine's layouts directly, no weight concat / cache copy):
  * ``qkv_rope_paged``: per-lane positions (decode lanes sit at ragged
    depths) and the native separate wq/wk/wv (D,H,dh) weights, streamed one
    (D, dh) head tile per grid step via clamped per-segment index maps.
  * ``oproj_ffn_swiglu``: the whole layer epilogue — attention out-proj +
    residual + RMSNorm + SwiGLU + residual — with the post-attention
    activation pinned in VMEM between the two residual adds; Wo and the
    FFN stream in tiles sized to the scoped VMEM (``lane_tile``), read out
    of the stacked (L, ...) weights by index maps offset by a
    scalar-prefetched layer index, so the layer scan slices none of them.
    (Wq/Wk/Wv cannot be streamed so: their per-head ``(D, dh)`` tiles need
    the ``(D, H*dh)`` view, which on the chip's tiled layout is a relayout
    of ``(D, H, dh)``, not a free view of the stack.)
  * ``ffn_swiglu(residual=False)``: the tensor-parallel partial form (down-
    proj partials psum'd across shards before the residual).

Every streamed tile's last two dims are 128-aligned or whole, as Mosaic
requires; ``tests/test_tpu_compile.py`` compiles the paged kernels for a
v5e chip at granite-8b widths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


# ----------------------------------------------------------------------
# norm + qkv + rope
# ----------------------------------------------------------------------

def _qkv_kernel(pos_ref, x_ref, scale_ref, w_ref, o_ref, *, dh, n_q, n_kv,
                theta, rope_frac):
    h = pl.program_id(0)
    xn = _rms(x_ref[...], scale_ref[...])                  # (B, D) f32
    y = jnp.dot(xn, w_ref[:, 0, :].astype(jnp.float32))    # (B, dh)

    rot = int(dh * rope_frac) - int(dh * rope_frac) % 2
    pos = pos_ref[0].astype(jnp.float32)
    di = jax.lax.iota(jnp.float32, rot // 2)
    inv = jnp.exp(-jnp.log(theta) * (2.0 * di / rot))
    ang = pos * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    y1, y2, yp = y[:, : rot // 2], y[:, rot // 2: rot], y[:, rot:]
    yr = jnp.concatenate([y1 * cos - y2 * sin, y2 * cos + y1 * sin, yp], axis=-1)
    is_v = h >= (n_q + n_kv)
    o_ref[0] = jnp.where(is_v, y, yr).astype(o_ref.dtype)


def qkv_rope(x, norm_scale, w_qkv, pos, *, n_q, n_kv, dh, theta=10000.0,
             rope_frac=1.0, interpret=False):
    """x (B,D); w_qkv (D, (n_q+2*n_kv)*dh), column-blocked one head per step.

    Returns (H_total, B, dh) with RoPE applied to q and k heads (v skipped).
    """
    B, D = x.shape
    H = n_q + 2 * n_kv
    assert w_qkv.shape == (D, H * dh)
    kernel = functools.partial(_qkv_kernel, dh=dh, n_q=n_q, n_kv=n_kv,
                               theta=theta, rope_frac=rope_frac)
    return pl.pallas_call(
        kernel,
        grid=(H,),
        in_specs=[
            pl.BlockSpec((1,), lambda h: (0,)),
            pl.BlockSpec((B, D), lambda h: (0, 0)),
            pl.BlockSpec((D,), lambda h: (0,)),
            pl.BlockSpec((D, 1, dh), lambda h: (0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, B, dh), lambda h: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, B, dh), x.dtype),
        interpret=interpret,
        name="qkv_rope",
    )(jnp.asarray(pos, jnp.int32).reshape(1), x, norm_scale,
      w_qkv.reshape(D, H, dh))


# ----------------------------------------------------------------------
# VMEM sizing
# ----------------------------------------------------------------------

# Bytes of one streamed weight tile. Each grid step holds two buffers of
# every streamed tile plus its f32 copy, and v5e's scoped-VMEM limit is
# 16 MiB; 1 MiB tiles keep the epilogue's four streams under it.
WEIGHT_TILE_BYTES = 1 << 20


def lane_tile(n: int, bytes_per_lane: int) -> int:
    """Largest multiple-of-128 divisor of ``n`` whose tile of
    ``bytes_per_lane * tile`` bytes fits ``WEIGHT_TILE_BYTES`` (at least
    128). Widths that are not a multiple of 128 (the reduced test configs)
    stream whole: Mosaic accepts a block dim that equals the array's."""
    if n % 128:
        return n
    best = 128
    for t in range(128, n + 1, 128):
        if n % t == 0 and t * bytes_per_lane <= WEIGHT_TILE_BYTES:
            best = t
    return best


# ----------------------------------------------------------------------
# paged-native norm + qkv + rope (per-lane positions, unconcatenated weights)
# ----------------------------------------------------------------------

def _qkv_paged_kernel(pos_ref, x_ref, scale_ref, inv_ref, sign_ref, wq_ref,
                      wk_ref, wv_ref, o_ref, *, n_q, n_kv):
    h = pl.program_id(0)
    xn = _rms(x_ref[...], scale_ref[...])                  # (B, D) f32

    # per-lane rotary: angles from each lane's own position (decode lanes sit
    # at ragged depths in the paged pool — there is no shared position
    # scalar). Rotate-half as a lane roll: [y1, y2] -> [y2, y1], with the
    # sign folded into sin, gives [y1 cos - y2 sin, y2 cos + y1 sin].
    ang = pos_ref[...].astype(jnp.float32) * inv_ref[...]  # (B, dh)
    cos, sin = jnp.cos(ang), jnp.sin(ang) * sign_ref[...]

    def proj(w_ref):
        return jnp.dot(xn, w_ref[...].astype(jnp.float32))  # (B, dh)

    def rope(y):
        return y * cos + pltpu.roll(y, y.shape[-1] // 2, 1) * sin

    # all three weight tiles are VMEM-resident each step, but their index
    # maps clamp outside their own segment — Pallas only re-DMAs a tile when
    # its mapped index CHANGES, so each weight byte streams exactly once
    @pl.when(h < n_q)
    def _q():
        o_ref[0] = rope(proj(wq_ref)).astype(o_ref.dtype)

    @pl.when((h >= n_q) & (h < n_q + n_kv))
    def _k():
        o_ref[0] = rope(proj(wk_ref)).astype(o_ref.dtype)

    @pl.when(h >= n_q + n_kv)
    def _v():
        o_ref[0] = proj(wv_ref).astype(o_ref.dtype)


def qkv_rope_paged(x, norm_scale, wq, wk, wv, pos, *, theta=10000.0,
                   interpret=False):
    """RMSNorm + QKV + per-lane full RoPE for the paged decode step.

    x (B,D); wq (D,n_q,dh), wk/wv (D,n_kv,dh) — the engine's NATIVE attention
    param layout, streamed one head per grid step as a ``(D, dh)`` column
    tile of the free ``(D, n*dh)`` view (no fused [Wq|Wk|Wv] concat); pos
    (B,) int32 per-lane positions. Returns (q (B,n_q,dh), k (B,n_kv,dh),
    v (B,n_kv,dh)) with RoPE applied to q and k.
    """
    import numpy as np
    B, D = x.shape
    _, n_q, dh = wq.shape
    n_kv = wk.shape[1]
    H = n_q + 2 * n_kv
    assert dh % 2 == 0, dh
    # host-side inv_freq with the exact numpy arithmetic of
    # models.layers._rope_angles, so fused and XLA paths agree bit-for-bit;
    # tiled twice across dh to match the lane-rolled rotate-half
    inv = (1.0 / (theta ** (np.arange(0, dh, 2) / dh))).astype(np.float32)
    inv_full = np.concatenate([inv, inv])[None]
    sign = np.concatenate([-np.ones(dh // 2), np.ones(dh // 2)]
                          ).astype(np.float32)[None]
    kernel = functools.partial(_qkv_paged_kernel, n_q=n_q, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(H,),
        in_specs=[
            pl.BlockSpec((B, 1), lambda h: (0, 0)),
            pl.BlockSpec((B, D), lambda h: (0, 0)),
            pl.BlockSpec((1, D), lambda h: (0, 0)),
            pl.BlockSpec((1, dh), lambda h: (0, 0)),
            pl.BlockSpec((1, dh), lambda h: (0, 0)),
            pl.BlockSpec((D, dh), lambda h: (0, jnp.minimum(h, n_q - 1))),
            pl.BlockSpec((D, dh),
                         lambda h: (0, jnp.clip(h - n_q, 0, n_kv - 1))),
            pl.BlockSpec((D, dh),
                         lambda h: (0, jnp.clip(h - n_q - n_kv, 0, n_kv - 1))),
        ],
        out_specs=pl.BlockSpec((1, B, dh), lambda h: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, B, dh), x.dtype),
        interpret=interpret,
        name="qkv_rope_paged",
    )(jnp.asarray(pos, jnp.int32).reshape(B, 1), x, norm_scale.reshape(1, D),
      jnp.asarray(inv_full), jnp.asarray(sign), wq.reshape(D, n_q * dh),
      wk.reshape(D, n_kv * dh), wv.reshape(D, n_kv * dh))
    q = out[:n_q].transpose(1, 0, 2)
    k = out[n_q:n_q + n_kv].transpose(1, 0, 2)
    v = out[n_q + n_kv:].transpose(1, 0, 2)
    return q, k, v


# ----------------------------------------------------------------------
# norm + SwiGLU FFN + residual
# ----------------------------------------------------------------------

def _ffn_kernel(x_ref, scale_ref, wg_ref, wu_ref, wo_ref, o_ref, acc_ref,
                *, nf, residual):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xn = _rms(x_ref[...], scale_ref[...])                   # (B, D) f32
    g = jnp.dot(xn, wg_ref[...].astype(jnp.float32))        # (B, bf)
    u = jnp.dot(xn, wu_ref[...].astype(jnp.float32))
    hidden = g * jax.nn.sigmoid(g) * u                      # silu(g)*u
    acc_ref[...] += jnp.dot(hidden, wo_ref[...].astype(jnp.float32))

    @pl.when(j == nf - 1)
    def _done():
        out = acc_ref[...]
        if residual:
            out = x_ref[...].astype(jnp.float32) + out
        o_ref[...] = out.astype(o_ref.dtype)


def ffn_swiglu(x, norm_scale, w_gate, w_up, w_down, *, block_f=None,
               residual=True, interpret=False):
    """x (B,D) -> x + SwiGLU(RMSNorm(x)); single pass over FFN weights.

    ``residual=False`` returns just SwiGLU(RMSNorm(x)) — the tensor-parallel
    partial form, where the down-projection output must be psum'd across the
    shards BEFORE the residual add (node/execution.py fused TP path).
    ``block_f`` defaults to the widest tile inside ``WEIGHT_TILE_BYTES``.
    """
    B, D = x.shape
    F = w_gate.shape[1]
    bf = min(block_f or lane_tile(F, D * w_gate.dtype.itemsize), F)
    assert F % bf == 0
    nf = F // bf
    kernel = functools.partial(_ffn_kernel, nf=nf, residual=residual)
    return pl.pallas_call(
        kernel,
        grid=(nf,),
        in_specs=[
            pl.BlockSpec((B, D), lambda j: (0, 0)),
            pl.BlockSpec((1, D), lambda j: (0, 0)),
            pl.BlockSpec((D, bf), lambda j: (0, j)),
            pl.BlockSpec((D, bf), lambda j: (0, j)),
            pl.BlockSpec((bf, D), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((B, D), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, D), jnp.float32)],
        interpret=interpret,
        name="ffn_swiglu",
    )(x, norm_scale.reshape(1, D), w_gate, w_up, w_down)


# ----------------------------------------------------------------------
# out-proj + residual + norm + SwiGLU FFN + residual (the layer epilogue)
# ----------------------------------------------------------------------

def _oproj_ffn_kernel(layer_ref, x_ref, attn_ref, wo_ref, scale_ref, wg_ref,
                      wu_ref, wd_ref, o_ref, y_ref, acc_ref, *, n_o, n_f):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # steps [0, n_o): attention out-proj, one row tile of Wo per step
    @pl.when(i < n_o)
    def _oproj():
        acc_ref[...] += jnp.dot(attn_ref[...].astype(jnp.float32),
                                wo_ref[...].astype(jnp.float32))

    @pl.when(i == n_o - 1)
    def _residual():
        # y = x + attn @ Wo persists in VMEM as both the FFN-norm input and
        # the final residual base
        y_ref[...] = x_ref[...].astype(jnp.float32) + acc_ref[...]
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # steps [n_o, n_o + n_f): SwiGLU FFN, one hidden tile per step
    @pl.when(i >= n_o)
    def _ffn():
        y = y_ref[...]
        xn = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                               + 1e-6) * scale_ref[...].astype(jnp.float32)
        g = jnp.dot(xn, wg_ref[...].astype(jnp.float32))    # (B, bf)
        u = jnp.dot(xn, wu_ref[...].astype(jnp.float32))
        hidden = g * jax.nn.sigmoid(g) * u
        acc_ref[...] += jnp.dot(hidden, wd_ref[...].astype(jnp.float32))

    @pl.when(i == n_o + n_f - 1)
    def _done():
        o_ref[...] = (y_ref[...] + acc_ref[...]).astype(o_ref.dtype)


def oproj_ffn_swiglu(x, attn_out, w_o, norm_scale, w_gate, w_up, w_down,
                     layer, *, block_f=None, interpret=False):
    """The whole epilogue of decoder layer ``layer`` in one kernel:

        y = x + attn_out @ w_o[layer]          (attention out-proj + residual)
        return y + SwiGLU(RMSNorm(y))          (FFN + residual)

    x (B,D); attn_out (B, Hq*dh); the engine's stacked weights w_o
    (L, Hq*dh, D) (its native (L,Hq,dh,D) ``wo`` reshaped, no copy),
    w_gate/w_up (L, D, F), w_down (L, F, D); ``layer`` an int32 scalar on
    the scalar-prefetch channel. Each weight streams from its free 2-D view
    ``(L*rows, cols)``, its index map offset by the layer's row blocks, so
    no layer is sliced out. A single layer's weights are ``w[None]`` with
    layer 0. One sequential grid streams Wo in row tiles, then the FFN in
    ``block_f``-wide hidden tiles; every weight byte is read once and ``y``
    never round-trips to HBM between out-proj and FFN. Each index map
    clamps outside its own phase, so the first FFN tiles prefetch during
    the out-proj. Wo's tile, and ``block_f`` by default, are the widest
    inside ``WEIGHT_TILE_BYTES``.
    """
    B, D = x.shape
    HD = attn_out.shape[1]
    L, _, F = w_gate.shape
    bo = lane_tile(HD, D * w_o.dtype.itemsize)
    bf = min(block_f or lane_tile(F, D * w_gate.dtype.itemsize), F)
    assert HD % bo == 0 and F % bf == 0, (HD, bo, F, bf)
    n_o, n_f = HD // bo, F // bf
    kernel = functools.partial(_oproj_ffn_kernel, n_o=n_o, n_f=n_f)

    def o_idx(i):
        return jnp.minimum(i, n_o - 1)

    def f_idx(i):
        return jnp.maximum(i - n_o, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_o + n_f,),
        in_specs=[
            pl.BlockSpec((B, D), lambda i, l: (0, 0)),
            pl.BlockSpec((B, bo), lambda i, l: (0, o_idx(i))),
            pl.BlockSpec((bo, D), lambda i, l: (l[0] * n_o + o_idx(i), 0)),
            pl.BlockSpec((1, D), lambda i, l: (0, 0)),
            pl.BlockSpec((D, bf), lambda i, l: (l[0], f_idx(i))),
            pl.BlockSpec((D, bf), lambda i, l: (l[0], f_idx(i))),
            pl.BlockSpec((bf, D), lambda i, l: (l[0] * n_f + f_idx(i), 0)),
        ],
        out_specs=pl.BlockSpec((B, D), lambda i, l: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((B, D), jnp.float32),
            pltpu.VMEM((B, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), x.dtype),
        interpret=interpret,
        name="oproj_ffn_swiglu",
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, attn_out,
      w_o.reshape(L * HD, D), norm_scale.reshape(1, D),
      w_gate.reshape(L * D, F), w_up.reshape(L * D, F),
      w_down.reshape(L * F, D))
