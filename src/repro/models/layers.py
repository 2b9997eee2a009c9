"""Shared neural-net layers: norms, RoPE variants, attention, FFN, MoE.

Attention uses a *streaming block* formulation (`block_attention`): the set of
valid (q-block, kv-block) pairs is enumerated statically in Python (causal /
sliding-window), and a `lax.scan` streams through them with an online-softmax
accumulator. This is the pure-JAX analogue of the paper's streaming-dataflow
pipeline (and of the Pallas flash kernel in kernels/flash_attention): it does
exactly the useful FLOPs — masked-out blocks are never computed — and bounds
activation memory to one (block x block) tile.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * scale


def layer_norm(x, scale, bias, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * scale + bias


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "rms":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def norm_specs(cfg: ModelConfig, d=None):
    from repro.models.common import spec
    d = d or cfg.d_model
    out = {"scale": spec((d,), ("embed",), init="ones")}
    if cfg.norm == "ln":
        out["bias"] = spec((d,), ("embed",), init="zeros")
    return out


# ----------------------------------------------------------------------
# RoPE (full / partial / m-rope)
# ----------------------------------------------------------------------

def _rope_angles(positions, rot_dim, theta):
    """positions (..., S) -> cos/sin of shape (..., S, rot_dim//2)."""
    inv_freq = 1.0 / (theta ** (np.arange(0, rot_dim, 2) / rot_dim))
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def _rotate_half(x, cos, sin):
    # llama-style: split last dim in halves
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(cfg: ModelConfig, x, positions):
    """x: (B, S, H, dh). positions: (B, S) int32, or (3, B, S) for m-rope."""
    if cfg.rope_style == "none":
        return x
    dh = x.shape[-1]
    rot_dim = int(dh * cfg.rope_fraction) if cfg.rope_style == "partial" else dh
    rot_dim -= rot_dim % 2
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    dt = x.dtype

    if cfg.rope_style == "mrope":
        if positions.ndim == 2:  # text-only: same stream for all 3 sections
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        cos, sin = _rope_angles(positions, rot_dim, cfg.rope_theta)  # (3,B,S,rot/2)
        secs = cfg.mrope_sections
        assert sum(secs) == rot_dim // 2, (secs, rot_dim)
        cos = jnp.concatenate(
            [cos[i, ..., sum(secs[:i]):sum(secs[: i + 1])] for i in range(3)], axis=-1
        )
        sin = jnp.concatenate(
            [sin[i, ..., sum(secs[:i]):sum(secs[: i + 1])] for i in range(3)], axis=-1
        )
    else:
        cos, sin = _rope_angles(positions, rot_dim, cfg.rope_theta)  # (B,S,rot/2)

    cos = cos[..., None, :].astype(jnp.float32)  # (B,S,1,rot/2)
    sin = sin[..., None, :].astype(jnp.float32)
    xr = _rotate_half(xr.astype(jnp.float32), cos, sin).astype(dt)
    return jnp.concatenate([xr, xp], axis=-1) if xp.shape[-1] else xr


# ----------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------

def _gqa_scores(qb, kb, scale):
    # qb (B,bq,Hkv,G,dh), kb (B,bk,Hkv,dh) -> (B,Hkv,G,bq,bk) fp32
    return jnp.einsum(
        "bqhgd,bkhd->bhgqk", qb, kb, preferred_element_type=jnp.float32
    ) * scale


def naive_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """Oracle quadratic attention. q (B,Sq,Hq,dh), k/v (B,Sk,Hkv,dh)."""
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, dv = v.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, dh)
    s = _gqa_scores(qg, k, 1.0 / math.sqrt(dh))      # (B,Hkv,G,Sq,Sk)
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, Hq, dv)


def _block_pairs(nq, nk, block, *, causal, window, q_offset_blocks=0):
    """Statically enumerate valid (qi, kj) block pairs."""
    pairs = []
    for i in range(nq):
        gi = i + q_offset_blocks
        for j in range(nk):
            if causal and j > gi:
                continue
            if window and (gi - j) * block >= window + block:
                continue
            pairs.append((i, j))
    return pairs


def block_attention(q, k, v, *, causal=True, window=0, block=1024, q_offset=0):
    """Streaming-block attention with online softmax; exact-FLOP causal/SWA.

    Shapes as naive_attention. S must be divisible by block (shapes in this
    framework are powers of two; block defaults to 1024).
    """
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, dv = v.shape
    if (Sq <= 2 * block and Sk <= 2 * block) or Sq % block or Sk % block:
        # small, or non-block-aligned (e.g. cross-attention to 1500 frames)
        return naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    nq, nk = Sq // block, Sk // block
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(dh)
    pairs = _block_pairs(nq, nk, block, causal=causal, window=window,
                         q_offset_blocks=q_offset // block)
    ii = jnp.array([p[0] for p in pairs], jnp.int32)
    jj = jnp.array([p[1] for p in pairs], jnp.int32)

    qg = q.reshape(B, Sq, Hkv, G, dh)
    acc0 = jnp.zeros((B, Sq, Hkv, G, dv), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)

    def step(carry, ij):
        acc, m, l = carry
        i, j = ij
        qb = jax.lax.dynamic_slice_in_dim(qg, i * block, block, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        s = _gqa_scores(qb, kb, scale)                    # (B,Hkv,G,bq,bk)
        qpos = i * block + jnp.arange(block)[:, None] + q_offset
        kpos = j * block + jnp.arange(block)[None, :]
        mask = jnp.ones((block, block), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, -jnp.inf)

        mb = jax.lax.dynamic_slice_in_dim(m, i * block, block, axis=3)
        lb = jax.lax.dynamic_slice_in_dim(l, i * block, block, axis=3)
        ab = jax.lax.dynamic_slice_in_dim(acc, i * block, block, axis=1)

        m_new = jnp.maximum(mb, s.max(axis=-1))
        # guard fully-masked rows (can't happen for valid pairs, but keep safe)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(mb), jnp.exp(mb - m_safe), 0.0)
        l_new = lb * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), vb,
                        preferred_element_type=jnp.float32)
        a_new = ab * alpha.transpose(0, 3, 1, 2)[..., None] + pv

        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new, i * block, axis=1)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, i * block, axis=3)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, i * block, axis=3)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (ii, jj))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, Sq, Hq, dv).astype(q.dtype)


def attention(cfg: ModelConfig, q, k, v, *, causal=True, window=0, q_offset=0):
    block = cfg.attn_chunk
    if q.shape[1] > 2 * block:
        return block_attention(q, k, v, causal=causal, window=window,
                               block=block, q_offset=q_offset)
    return naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, valid_mask):
    """Single-token decode. q (B,1,Hq,dh); caches (B,S,Hkv,dh);
    valid_mask (B,S) bool."""
    B, _, Hq, dh = q.shape
    _, S, Hkv, dv = v_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    s = jnp.where(valid_mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, dv).astype(q.dtype)


# ----------------------------------------------------------------------
# FFN
# ----------------------------------------------------------------------

def ffn_specs(cfg: ModelConfig, d_ff=None):
    from repro.models.common import spec
    D, F = cfg.d_model, d_ff or cfg.d_ff
    p = {}
    if cfg.act in ("swiglu", "geglu"):
        p["wi_gate"] = spec((D, F), ("embed", "ffn"))
        p["wi_up"] = spec((D, F), ("embed", "ffn"))
    else:
        p["wi"] = spec((D, F), ("embed", "ffn"))
    p["wo"] = spec((F, D), ("ffn", "embed"))
    if cfg.mlp_bias:
        if cfg.act in ("swiglu", "geglu"):
            p["bi_gate"] = spec((F,), ("ffn",), init="zeros")
            p["bi_up"] = spec((F,), ("ffn",), init="zeros")
        else:
            p["bi"] = spec((F,), ("ffn",), init="zeros")
        p["bo"] = spec((D,), ("embed",), init="zeros")
    return p


def _act(cfg, x):
    if cfg.act == "swiglu":
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=True)


def ffn_apply(cfg: ModelConfig, p, x):
    if cfg.act in ("swiglu", "geglu"):
        g = x @ p["wi_gate"]
        u = x @ p["wi_up"]
        if cfg.mlp_bias:
            g = g + p["bi_gate"]
            u = u + p["bi_up"]
        h = _act(cfg, g) * u
    else:
        h = x @ p["wi"]
        if cfg.mlp_bias:
            h = h + p["bi"]
        h = _act(cfg, h)
    y = h @ p["wo"]
    if cfg.mlp_bias:
        y = y + p["bo"]
    return y


# ----------------------------------------------------------------------
# MoE (sort-based token dispatch — O(T*k*D), no quadratic einsum dispatch)
# ----------------------------------------------------------------------

def moe_specs(cfg: ModelConfig):
    from repro.models.common import spec
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    p = {
        "router": spec((D, E), ("embed", "experts_r"), dtype=jnp.float32),
        "experts": {
            "wi_gate": spec((E, D, F), ("experts", "embed", "expert_ffn"),
                            fan_in_axes=(1,)),
            "wi_up": spec((E, D, F), ("experts", "embed", "expert_ffn"),
                          fan_in_axes=(1,)),
            "wo": spec((E, F, D), ("experts", "expert_ffn", "embed"),
                       fan_in_axes=(1,)),
        },
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "wi_gate": spec((D, Fs), ("embed", "ffn")),
            "wi_up": spec((D, Fs), ("embed", "ffn")),
            "wo": spec((Fs, D), ("ffn", "embed")),
        }
    return p


def moe_apply_ep_local(cfg: ModelConfig, p, x, mesh):
    """Expert-parallel MoE with *local* dispatch (beyond-paper §Perf).

    Insight: under tensor parallelism the activations entering the MoE are
    already replicated across the 'model' axis. With experts sharded over
    'model', every model-rank can therefore select/rank/scatter the tokens
    bound for ITS local experts entirely locally — no global sort, no
    cross-device scatter. The only collective is one psum of the combined
    output over 'model' (same shape/cost as the TP FFN all-reduce it
    replaces). GSPMD's gather-heavy lowering of the global sort-based
    dispatch disappears.
    """
    E, K = cfg.n_experts, cfg.top_k
    msize = mesh.shape["model"]
    assert E % msize == 0
    E_loc = E // msize
    B, S, D = x.shape
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    router_w = p["router"]
    wg, wu, wo = (p["experts"]["wi_gate"], p["experts"]["wi_up"],
                  p["experts"]["wo"])

    def body(xt, rw, wg_l, wu_l, wo_l):
        # xt (B_loc, S, D) model-replicated; expert weights local (E_loc,...)
        Bl, Sl, Dl = xt.shape
        T = Bl * Sl
        xf = xt.reshape(T, Dl)
        rank = jax.lax.axis_index("model")
        my_first = rank * E_loc

        logits = xf.astype(jnp.float32) @ rw
        gates = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(gates, K)
        if cfg.family == "moe":
            topw = topw / topw.sum(-1, keepdims=True)
        topw = topw * cfg.routed_scale

        from repro.distributed import ctx as _ctx
        cap = _ctx.perf().capacity_factor or cfg.capacity_factor
        C = max(1, int(math.ceil(T * K / E * cap)))
        TK = T * K
        eid = topi.reshape(TK)
        tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
        w = topw.reshape(TK)

        mine = (eid >= my_first) & (eid < my_first + E_loc)
        eloc = jnp.where(mine, eid - my_first, E_loc)      # E_loc = drop row
        order = jnp.argsort(eloc, stable=True)             # local sort
        el_s, tid_s, w_s = eloc[order], tid[order], w[order]
        counts = jnp.sum(jax.nn.one_hot(el_s, E_loc + 1, dtype=jnp.int32),
                         axis=0)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(TK, dtype=jnp.int32) - starts[el_s]
        keep = (el_s < E_loc) & (pos < C)
        pos_c = jnp.where(keep, pos, C)
        row = jnp.where(keep, el_s, E_loc)

        xe = jnp.zeros((E_loc + 1, C + 1, Dl), xt.dtype)
        xe = xe.at[row, pos_c].set(jnp.where(keep[:, None], xf[tid_s], 0))
        xe = xe[:E_loc, :C]

        h_g = jnp.einsum("ecd,edf->ecf", xe, wg_l)
        h_u = jnp.einsum("ecd,edf->ecf", xe, wu_l)
        h = _act(cfg, h_g) * h_u
        ye = jnp.einsum("ecf,efd->ecd", h, wo_l)

        yc = ye[jnp.minimum(row, E_loc - 1), jnp.minimum(pos_c, C - 1)]
        yc = yc * (w_s * keep.astype(w_s.dtype))[:, None].astype(yc.dtype)
        out = jnp.zeros((T, Dl), jnp.float32).at[tid_s].add(
            yc.astype(jnp.float32))
        out = jax.lax.psum(out, "model")                   # the only collective
        return out.astype(xt.dtype).reshape(Bl, Sl, Dl)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(dp if dp else None, None, None),
                  jax.sharding.PartitionSpec(None, None),
                  jax.sharding.PartitionSpec("model", None, None),
                  jax.sharding.PartitionSpec("model", None, None),
                  jax.sharding.PartitionSpec("model", None, None)),
        out_specs=jax.sharding.PartitionSpec(dp if dp else None, None, None),
        check_vma=False,
    )
    out = fn(x, router_w, wg, wu, wo)
    if cfg.n_shared_experts:
        out = out + ffn_apply(cfg, p["shared"], x)
    return out


def moe_apply(cfg: ModelConfig, p, x):
    """x (B,S,D) -> (B,S,D). Top-k routing with capacity, sort-based dispatch."""
    from repro.distributed import ctx as _c
    mesh = _c.current_mesh()
    if (_c.perf().moe_ep_local and mesh is not None
            and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0):
        return moe_apply_ep_local(cfg, p, x, mesh)
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T,E)
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(gates, K)                     # (T,K)
    if cfg.family == "moe":            # mixtral renormalizes over top-k
        topw = topw / topw.sum(-1, keepdims=True)
    topw = topw * cfg.routed_scale

    from repro.distributed import ctx as _ctx
    cap = _ctx.perf().capacity_factor or cfg.capacity_factor
    C = max(1, int(math.ceil(T * K / E * cap)))
    TK = T * K
    eid = topi.reshape(TK)
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    w = topw.reshape(TK)

    order = jnp.argsort(eid, stable=True)
    eid_s, tid_s, w_s = eid[order], tid[order], w[order]
    # rank within expert = own index - start of this expert's run
    counts = jnp.sum(jax.nn.one_hot(eid_s, E, dtype=jnp.int32), axis=0)  # (E,)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(TK, dtype=jnp.int32) - starts[eid_s]
    keep = pos < C
    pos_c = jnp.where(keep, pos, C)                          # overflow slot C

    # scatter tokens -> (E, C+1, D); slot C collects dropped tokens
    xe = jnp.zeros((E, C + 1, D), x.dtype)
    xe = xe.at[eid_s, pos_c].set(jnp.where(keep[:, None], xt[tid_s], 0))
    xe = xe[:, :C]                                           # (E,C,D)
    xe = _ctx.constrain_named("moe_dispatch", xe)

    h_g = jnp.einsum("ecd,edf->ecf", xe, p["experts"]["wi_gate"])
    h_u = jnp.einsum("ecd,edf->ecf", xe, p["experts"]["wi_up"])
    h = _act(cfg, h_g) * h_u
    ye = jnp.einsum("ecf,efd->ecd", h, p["experts"]["wo"])   # (E,C,D)
    ye = _ctx.constrain_named("moe_dispatch", ye)

    # gather back + combine
    yc = ye[eid_s, jnp.minimum(pos_c, C - 1)]                # (TK,D)
    yc = yc * (w_s * keep.astype(w_s.dtype))[:, None].astype(yc.dtype)
    out = jnp.zeros((T, D), jnp.float32).at[tid_s].add(yc.astype(jnp.float32))
    out = out.astype(x.dtype)

    if cfg.n_shared_experts:
        out = out + ffn_apply(cfg, p["shared"], xt)
    return out.reshape(B, S, D)
