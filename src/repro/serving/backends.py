"""Pluggable decode-step backends behind one paged-runner seam.

The engine's decode hot path is one jitted *paged extend* (scatter new K/V
into ``PagedKVCache`` block tables, attend, project). HOW that step executes
is now a backend choice:

  * ``XlaPagedBackend``   — the original pure-XLA body (``xla_paged_extend``;
    contiguous ``pool[tables]`` gather + masked softmax). Runs everywhere,
    bit-stable, and is the correctness reference every fused result is
    tested against.
  * ``FusedPagedBackend`` — the paper's streaming-dataflow claim (§III,
    Fig 6) realized with the repo's own Pallas kernels: per layer, a
    RMSNorm+QKV+RoPE prologue (``kernels/fused_decode.qkv_rope_paged``), a
    block-sparse paged flash-decode that gathers K/V straight from the block
    tables (``kernels/flash_attention.decode_paged`` — no contiguous cache
    copy ever materializes), and an out-proj+SwiGLU epilogue
    (``oproj_ffn_swiglu``) that keeps the inter-op activations in VMEM.
    Supported for the dense RMSNorm/SwiGLU/full-RoPE family; the
    single-token step (g=1 — greedy decode and the speculative draft loop)
    is fused, multi-token verify steps (g>1) fall back to the XLA body
    inside the same runner.

Select with ``make_runner(cfg, scratch_row, backend="fused")`` or any of the
threaded surfaces: ``ServingEngine(backend=)``, ``RDUNode(backend=)`` /
``node.execution.make_group_engine(backend=)``, ``launch/serve.py
--backend``, ``benchmarks/run.py --sweep-arrival --backend``.

Every compiled step is wrapped in a ``decode_kernel`` trace span (labelled
with the backend) and exposes ``step_cost_analysis()`` — the measured
HBM-traffic side of the Fig-6 fused-vs-unfused sweep. On the device the
step is the program ``decode_step``, and both bodies name the same parts
with ``jax.named_scope``: ``embed``, then per layer (the scan body)
``qkv_rope``, ``kv_scatter``, ``attention`` and ``oproj_ffn``, then
``head``. A profile attributes each device operation by program, then by
the innermost of these scopes in its ``op_name``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.obs import trace
from repro.serving.prefill import record_compile


# ----------------------------------------------------------------------
# XLA reference body
# ----------------------------------------------------------------------

def xla_paged_extend(cfg: ModelConfig, params, pk, pv, tables, lengths,
                     active, tokens, scratch_row: int):
    """g-token extend step against the paged pool (pure-XLA reference).

    pk/pv   (L, rows, block, Hkv, dh) pool arrays (rows includes scratch)
    tables  (B, maxb) int32 per-slot block tables (padded with scratch)
    lengths (B,) int32 tokens already cached per slot
    active  (B,) bool — lanes actually decoding this round; inactive lanes
            scatter their (garbage) K/V to the scratch block and their
            logits are ignored by the caller
    tokens  (B, g) int32 inputs at positions lengths..lengths+g-1
    Returns (logits (B,g,V), pk, pv).
    """
    from repro.models import layers as L
    from repro.models import transformer as T

    B, g = tokens.shape
    block = pk.shape[2]
    maxb = tables.shape[1]
    S = maxb * block
    with jax.named_scope("embed"):
        h = T.embed_tokens(cfg, params, tokens)                   # (B,g,D)
    positions = lengths[:, None] + jnp.arange(g, dtype=jnp.int32)[None]
    blk_idx = jnp.minimum(positions // block, maxb - 1)
    rows = jnp.take_along_axis(tables, blk_idx, axis=1)           # (B,g)
    rows = jnp.where(active[:, None], rows, jnp.int32(scratch_row))
    off = positions % block
    kpos = jnp.arange(S, dtype=jnp.int32)
    mask = kpos[None, None, :] <= positions[:, :, None]           # (B,g,S)
    moe = cfg.n_experts > 0
    Hq, dh = cfg.n_heads, cfg.head_dim

    def body(hh, xs):
        lp, kp, vp = xs                    # kp (rows, block, Hkv, dh)
        p = lp["attn"]
        with jax.named_scope("qkv_rope"):
            hn = L.apply_norm(cfg, p["norm"], hh)
            q = jnp.einsum("bsd,dhk->bshk", hn, p["wq"])
            k = jnp.einsum("bsd,dhk->bshk", hn, p["wk"])
            v = jnp.einsum("bsd,dhk->bshk", hn, p["wv"])
            if cfg.qkv_bias:
                q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
            q = L.apply_rope(cfg, q, positions)
            k = L.apply_rope(cfg, k, positions)
        with jax.named_scope("kv_scatter"):
            kp = kp.at[rows, off].set(k.astype(kp.dtype))
            vp = vp.at[rows, off].set(v.astype(vp.dtype))
        with jax.named_scope("attention"):
            kc = kp[tables].reshape(B, S, *kp.shape[2:])          # (B,S,Hkv,dh)
            vc = vp[tables].reshape(B, S, *vp.shape[2:])
            Hkv = kc.shape[2]
            qg = q.reshape(B, g, Hkv, Hq // Hkv, dh)
            s = jnp.einsum("bqhgd,bshd->bhgqs", qg, kc,
                           preferred_element_type=jnp.float32) / math.sqrt(dh)
            s = jnp.where(mask[:, None, None], s, -jnp.inf)
            pa = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhgqs,bshd->bqhgd", pa.astype(vc.dtype), vc,
                           preferred_element_type=jnp.float32)
            o = o.reshape(B, g, Hq, dh).astype(hh.dtype)
        with jax.named_scope("oproj_ffn"):
            y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
            if cfg.attn_out_bias:
                y = y + p["bo"]
            hh = hh + y
            hh = T._mlp(cfg, lp["mlp_norm"], lp["mlp"], hh, moe)
        return hh, (kp, vp)

    h, (pk, pv) = jax.lax.scan(body, h, (params["layers"], pk, pv))
    with jax.named_scope("head"):
        h = L.apply_norm(cfg, params["final_norm"], h)
        logits = T.unembed(cfg, params, h)
    return logits, pk, pv


# ----------------------------------------------------------------------
# Fused Pallas body (g = 1)
# ----------------------------------------------------------------------

def fused_paged_extend(cfg: ModelConfig, params, pk, pv, tables, lengths,
                       active, tokens, scratch_row: int,
                       interpret: Optional[bool] = None):
    """Single-token paged extend where every decoder layer runs as three
    Pallas calls: qkv_rope_paged -> decode_paged -> oproj_ffn_swiglu, with
    only the K/V scatter (one dynamic row write) left to XLA. Semantics are
    identical to ``xla_paged_extend`` with g=1 — including the masking
    convention: a lane attends positions ``kpos <= lengths``, i.e. ``len1 =
    lengths + 1`` valid cache slots after this step's scatter; inactive and
    empty lanes compute finite garbage the caller ignores.

    The layer scan slices no pool and stacks none back: it carries the
    donated pools and scatters layer ``l``'s new K/V into them in place,
    and ``decode_paged`` reads them as one free ``(L*rows, ...)`` view
    through tables offset by ``l * rows``. The epilogue streams layer ``l``
    of the stacked Wo and FFN weights by index map. Only Wq/Wk/Wv and the
    norm scales ride the scan's ``xs``: the prologue's per-head tiles need
    each layer's Wq/Wk/Wv relaid out as ``(D, H*dh)`` (not a free view on
    the chip's tiled layout), which XLA does per layer in on-chip memory."""
    from repro.kernels.fused_decode.kernel import (qkv_rope_paged,
                                                   oproj_ffn_swiglu)
    from repro.kernels.flash_attention.ops import decode_paged
    from repro.kernels.runtime import resolve_interpret
    from repro.models import layers as L
    from repro.models import transformer as T

    B, g = tokens.shape
    assert g == 1, "fused_paged_extend is the single-token hot path"
    n_layers, n_rows, block = pk.shape[:3]
    maxb = tables.shape[1]
    Hq, dh, D = cfg.n_heads, cfg.head_dim, cfg.d_model
    it = resolve_interpret(interpret)

    with jax.named_scope("embed"):
        h = T.embed_tokens(cfg, params, tokens)[:, 0]             # (B, D)
    pos = lengths                                                 # (B,)
    blk_idx = jnp.minimum(pos // block, maxb - 1)
    rows = jnp.take_along_axis(tables, blk_idx[:, None], axis=1)[:, 0]
    rows = jnp.where(active, rows, jnp.int32(scratch_row))
    off = pos % block
    len1 = lengths + 1
    lw = params["layers"]
    a, mlp = lw["attn"], lw["mlp"]
    wo = a["wo"].reshape(n_layers, Hq * dh, D)
    per_layer = (a["norm"]["scale"], a["wq"], a["wk"], a["wv"],
                 lw["mlp_norm"]["scale"])

    def body(carry, xs):
        hh, pk, pv = carry                 # pk (L, rows, block, Hkv, dh)
        (s_attn, wq, wk, wv, s_mlp), l = xs
        with jax.named_scope("qkv_rope"):
            q, k, v = qkv_rope_paged(hh, s_attn, wq, wk, wv, pos,
                                     theta=cfg.rope_theta, interpret=it)
        with jax.named_scope("kv_scatter"):
            pk = pk.at[l, rows, off].set(k.astype(pk.dtype))
            pv = pv.at[l, rows, off].set(v.astype(pv.dtype))
        with jax.named_scope("attention"):
            o = decode_paged(q, pk.reshape(-1, *pk.shape[2:]),
                             pv.reshape(-1, *pv.shape[2:]),
                             tables + l * n_rows, len1,
                             interpret=it)                        # (B,Hq,dh)
        with jax.named_scope("oproj_ffn"):
            hh = oproj_ffn_swiglu(hh, o.reshape(B, Hq * dh), wo, s_mlp,
                                  mlp["wi_gate"], mlp["wi_up"], mlp["wo"],
                                  l, interpret=it)
        return (hh, pk, pv), None

    (h, pk, pv), _ = jax.lax.scan(
        body, (h, pk, pv), (per_layer, jnp.arange(n_layers, dtype=jnp.int32)))
    with jax.named_scope("head"):
        h = L.apply_norm(cfg, params["final_norm"], h)[:, None]   # (B,1,D)
        logits = T.unembed(cfg, params, h)
    return logits, pk, pv


def fused_kernel_hbm_bytes(cfg: ModelConfig, batch: int, maxb: int,
                           block: int, kv_itemsize: int = 2,
                           p_itemsize: int = 4,
                           act_itemsize: int = 4) -> int:
    """Exact analytic HBM bytes streamed by the Pallas kernels in ONE fused
    extend step (g=1): grid x BlockSpec tile sizes, deduplicated wherever an
    index map is constant or clamped (Pallas re-DMAs a tile only when its
    mapped index changes). XLA's cost model treats custom calls as opaque,
    so the sweep's measured-traffic column adds this term for the fused
    backend."""
    B = batch
    Hq, Hkv, dh, D, F = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                         cfg.d_model, cfg.d_ff)
    H = Hq + 2 * Hkv
    rot = dh - dh % 2
    # prologue: activations/scale/pos/inv once; each weight head once; out
    prologue = (B * D + D + B + rot // 2) * act_itemsize \
        + D * H * dh * p_itemsize + H * B * dh * act_itemsize
    # paged attention: per (b, kv-head) the q group tile; per (b, h, j) one
    # K and one V pool tile; output group tile
    G = Hq // Hkv
    attn = (B * Hkv * G * dh * act_itemsize * 2          # q in + o out
            + B * Hkv * maxb * block * dh * kv_itemsize * 2)
    # epilogue: x/attn/wo/scale once; gate/up/down streamed once; out
    epilogue = (B * D + B * Hq * dh + D) * act_itemsize \
        + (Hq * dh * D + 3 * D * F) * p_itemsize + B * D * act_itemsize
    return cfg.n_layers * (prologue + attn + epilogue)


# ----------------------------------------------------------------------
# Backend objects + the runner
# ----------------------------------------------------------------------

def _decode_step(body, cfg: ModelConfig, scratch_row: int, **kw):
    """``body`` bound to one config as the function the runner jits: its
    name, ``decode_step``, names the program on the device."""
    def decode_step(p, pk, pv, tb, ln, ac, tk):
        return body(cfg, p, pk, pv, tb, ln, ac, tk, scratch_row, **kw)
    return decode_step


class PagedBackend:
    """One way to execute the paged extend step. Subclasses supply
    ``extend_fn(B, g)`` -> a traceable ``f(params, pk, pv, tables, lengths,
    active, tokens)`` the runner jits (with pool donation) per shape."""

    name = "?"

    def __init__(self, cfg: ModelConfig, scratch_row: int):
        self.cfg = cfg
        self.scratch_row = scratch_row

    def extend_fn(self, batch: int, g: int):
        raise NotImplementedError


class XlaPagedBackend(PagedBackend):
    """Today's pure-XLA step — the correctness reference."""

    name = "xla"

    def extend_fn(self, batch: int, g: int):
        return _decode_step(xla_paged_extend, self.cfg, self.scratch_row)


class FusedPagedBackend(PagedBackend):
    """Pallas fused decode path (see module docstring). g=1 steps fuse;
    g>1 (speculative verify) runs the XLA body under the same runner."""

    name = "fused"

    def __init__(self, cfg: ModelConfig, scratch_row: int,
                 interpret: Optional[bool] = None):
        super().__init__(cfg, scratch_row)
        self.interpret = interpret
        unsupported = []
        if cfg.n_experts > 0:
            unsupported.append("MoE FFN")
        if cfg.norm != "rms":
            unsupported.append(f"norm={cfg.norm!r}")
        if cfg.act != "swiglu":
            unsupported.append(f"act={cfg.act!r}")
        if cfg.rope_style != "full":
            unsupported.append(f"rope_style={cfg.rope_style!r}")
        if cfg.qkv_bias or cfg.attn_out_bias or cfg.mlp_bias:
            unsupported.append("attention/MLP biases")
        if unsupported:
            raise ValueError(
                "backend='fused' supports the dense RMSNorm/SwiGLU/full-RoPE "
                f"decoder family only; {cfg.name!r} needs "
                f"{', '.join(unsupported)} — use backend='xla'")

    def extend_fn(self, batch: int, g: int):
        if g > 1:
            return _decode_step(xla_paged_extend, self.cfg, self.scratch_row)
        return _decode_step(fused_paged_extend, self.cfg, self.scratch_row,
                            interpret=self.interpret)

    def kernel_hbm_bytes(self, batch: int, maxb: int, block: int,
                         kv_itemsize: int = 2) -> int:
        return fused_kernel_hbm_bytes(self.cfg, batch, maxb, block,
                                      kv_itemsize=kv_itemsize)


BACKENDS = {"xla": XlaPagedBackend, "fused": FusedPagedBackend}


def make_backend(backend, cfg: ModelConfig, scratch_row: int) -> PagedBackend:
    """'xla' / 'fused' / an already-built ``PagedBackend``."""
    if isinstance(backend, PagedBackend):
        return backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r} "
                         f"(choose from {sorted(BACKENDS)})")
    return BACKENDS[backend](cfg, scratch_row)


class PagedDecodeRunner:
    """jit-compiled paged prefill / extend for one backbone config.

    All experts of a Samba-CoE share the backbone (paper §II), so one runner
    — one compiled extend per (n_slots, g) — serves every expert. Shareable
    across engines to reuse the compile cache (the benchmark sweep does).
    The extend body comes from the selected ``PagedBackend``; every compiled
    call runs under a ``decode_kernel`` trace span labelled with it.
    """

    def __init__(self, cfg: ModelConfig, scratch_row: int, backend="xla"):
        if cfg.family not in ("dense", "moe"):
            raise ValueError("paged serving supports dense/moe families only")
        if cfg.sliding_window:
            raise ValueError("paged serving does not support sliding windows")
        if cfg.first_dense_layers:
            raise ValueError("paged serving: first_dense_layers unsupported")
        self.cfg = cfg
        self.scratch_row = scratch_row
        self.backend = make_backend(backend, cfg, scratch_row)
        self._prefill = {}                 # S -> jitted forward
        self._extend = {}                  # (B, g) -> jitted extend
        self._abstract: Dict[Tuple[int, int], tuple] = {}
        self._last_key: Optional[Tuple[int, int]] = None

    @property
    def backend_name(self) -> str:
        return self.backend.name

    def prefill_kv(self, params, tokens):
        """tokens (1,S) -> (last logits (V,), k, v each (L,S,Hkv,dh))."""
        from repro.models import transformer as T
        S = tokens.shape[1]
        if S not in self._prefill:
            record_compile("prefill_kv")
            cfg = self.cfg
            self._prefill[S] = jax.jit(lambda p, t: T.forward(
                cfg, p, {"tokens": t}, return_cache=True, last_only=True))
        logits, caches = self._prefill[S](params, tokens)
        k, v = caches[-1]
        return logits[:, -1][0], k[:, 0], v[:, 0]

    def _extend_jit(self, key):
        if key not in self._extend:
            record_compile("extend")
            self._extend[key] = jax.jit(self.backend.extend_fn(*key),
                                        donate_argnums=(1, 2))
        return self._extend[key]

    def extend(self, params, pk, pv, tables, lengths, active, tokens):
        key = tokens.shape
        fn = self._extend_jit(key)
        args = (params, pk, pv, jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(active), jnp.asarray(tokens))
        if key not in self._abstract:
            self._abstract[key] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.asarray(x).dtype), args)
        self._last_key = key
        with trace.span("decode_kernel", cat="kernel",
                        backend=self.backend.name, batch=key[0], g=key[1]):
            return fn(*args)

    def compiled_extend(self, key=None):
        """The compiled extend step for ``key`` (a ``tokens.shape``; default
        the most recent), lowered against the shapes it last ran with, or
        None when it never ran. With the persistent compile cache on, this
        re-reads the executable rather than compiling it again."""
        key = key or self._last_key
        if key is None or key not in self._abstract:
            return None
        return self._extend_jit(key).lower(*self._abstract[key]).compile()

    def step_cost_analysis(self, key=None) -> Optional[dict]:
        """XLA cost analysis ('bytes accessed', 'flops', ...) of a compiled
        extend step — the measured side of the Fig-6 sweep. ``key`` is a
        ``tokens.shape``; defaults to the most recent. Returns None when the
        step never ran or the backend offers no cost model. NOTE: Pallas
        kernels appear as opaque custom calls to XLA's model — add
        ``FusedPagedBackend.kernel_hbm_bytes`` for their traffic."""
        try:
            compiled = self.compiled_extend(key)
            cost = compiled.cost_analysis() if compiled else None
        except Exception:
            return None
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        return dict(cost) if cost else None


def make_runner(cfg: ModelConfig, scratch_row: int,
                backend="xla") -> PagedDecodeRunner:
    """The backend-selection seam: a single-device paged runner executing
    the chosen backend. (The TP analogue is
    ``node.execution.TPPagedDecodeRunner(cfg, scratch_row, mesh,
    backend=...)``.)"""
    return PagedDecodeRunner(cfg, scratch_row, backend=backend)
