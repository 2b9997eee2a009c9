"""AOT-compiled, bucketed, packed prefill for the serving engine.

Prefill is the compile-shape hazard of the serving stack: the decode step
runs one fixed ``(n_slots, g)`` shape forever, but every novel *prompt
length* used to hit ``jax.jit`` with a fresh ``(1, S)`` signature — a
multi-second XLA compile stall right on the TTFT critical path ("heavy
traffic from millions of users" means every length shows up eventually).
This module removes the hazard and amortizes the per-admit forward:

  * **power-of-two length buckets** (``default_buckets``): a packed prefill
    always runs at a bucket shape, so the engine compiles ``O(log max_len)``
    forwards total — all of them ahead of time at ``warmup()``;
  * **packing via segment ids**: several prompts ride in ONE ``(1, bucket)``
    call. The causal mask is blocked across segments
    (``(seg_i == seg_j) & (j <= i)``) and RoPE positions restart per
    segment, so each prompt's logits and K/V equal its own sequential
    ``prefill_kv`` call up to summation order (masked cross-segment scores
    contribute exact zeros; tests/test_prefill.py holds them to
    ``models.reference.F32_TOL`` at f32).
    Padding gets its own segment id — pad queries attend at least
    themselves, so no softmax row is fully masked and no NaN can leak
    through ``0 * NaN`` into real rows;
  * **donated scatter handoff**: the packed K/V lands in the paged pool via
    a per-bucket jitted scatter with ``donate_argnums=(0, 1)`` — the pool
    buffers are updated in place, decode state is handed off without a
    copy. Pad positions scatter to the pool's scratch block.

``record_compile``/``compile_count`` is the compile-accounting hook the
recompile-regression test (and ``benchmarks/run.py --sweep-prefill``) keys
on: every site that triggers a fresh XLA compile in the serving path —
bucketed prefill, scatter, sequential ``prefill_kv``, decode extend —
reports here, so "zero compilations after warmup" is a testable invariant
rather than a hope.
"""
from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


# ----------------------------------------------------------------------
# Compile accounting
# ----------------------------------------------------------------------

_compile_lock = threading.Lock()
_compile_counts: Dict[str, int] = {}


def record_compile(site: str) -> None:
    """Report one fresh XLA compilation from ``site`` (e.g. ``"packed_
    prefill"``, ``"prefill_kv"``, ``"extend"``). Call exactly where a new
    shape enters a jit/lower cache."""
    with _compile_lock:
        _compile_counts[site] = _compile_counts.get(site, 0) + 1


def compile_count(site: Optional[str] = None) -> int:
    """Total compiles recorded (optionally for one site) since process start
    or the last ``reset_compile_counts``."""
    with _compile_lock:
        if site is not None:
            return _compile_counts.get(site, 0)
        return sum(_compile_counts.values())


def compile_counts() -> Dict[str, int]:
    """Per-site snapshot of the compile counters."""
    with _compile_lock:
        return dict(_compile_counts)


def reset_compile_counts() -> None:
    with _compile_lock:
        _compile_counts.clear()


# ----------------------------------------------------------------------
# Buckets and packing plans
# ----------------------------------------------------------------------

def default_buckets(max_len: int, min_bucket: int = 16) -> Tuple[int, ...]:
    """Power-of-two buckets ``min_bucket, 2*min_bucket, ...`` up to the
    first bucket covering ``max_len``."""
    if max_len < 1:
        raise ValueError("max_len must be >= 1")
    out = [min_bucket]
    while out[-1] < max_len:
        out.append(out[-1] * 2)
    return tuple(out)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket covering ``n`` tokens."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} tokens exceed the largest bucket {buckets[-1]}")


def plan_packs(lengths: Sequence[int], buckets: Sequence[int],
               max_segments: int) -> List[List[int]]:
    """Greedy in-order chunking of prompt ``lengths`` into packed prefill
    calls: consecutive prompts share a call while their total fits the
    largest bucket and the segment count stays within ``max_segments``.
    Returns lists of indices into ``lengths`` (order preserved — admission
    order is part of the scheduler's fairness contract)."""
    cap = buckets[-1]
    chunks: List[List[int]] = []
    cur: List[int] = []
    total = 0
    for i, n in enumerate(lengths):
        if n > cap:
            raise ValueError(f"prompt {i} ({n} tokens) exceeds bucket cap {cap}")
        if cur and (total + n > cap or len(cur) >= max_segments):
            chunks.append(cur)
            cur, total = [], 0
        cur.append(i)
        total += n
    if cur:
        chunks.append(cur)
    return chunks


# ----------------------------------------------------------------------
# The packed forward
# ----------------------------------------------------------------------

def packed_attention(q, k, v, seg):
    """Causal attention blocked across segments: query ``i`` attends key
    ``j`` iff ``j <= i`` AND both flat positions carry the same segment id.
    Shapes: q (B,Sq,Hq,dh), k/v (B,Sk,Hkv,dh), seg (B,Sq) int32 (GQA via
    head grouping, same contraction order as the sequential dense path so
    per-segment results stay bit-identical)."""
    B, Sq, Hq, dh = q.shape
    _, Sk, Hkv, dv = v.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(dh))
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = (kpos <= qpos) & (seg[0][:, None] == seg[0][None, :])
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, Hq, dv)


def packed_prefill_fn(cfg: ModelConfig):
    """Traceable packed prefill forward for one backbone config.

    ``f(params, tokens (1,S), seg (1,S), pos (1,S), last_idx (P,)) ->
    (logits (P, V), k (L,S,Hkv,dh), v (L,S,Hkv,dh))`` where ``S`` is the
    bucket, ``seg`` carries segment ids (pad = a distinct id), ``pos``
    restarts at 0 per segment (fed to RoPE), and ``last_idx`` points at each
    segment's final token (padded rows gather position 0 — callers ignore
    them). The body mirrors ``models.transformer`` layer math exactly; only
    the attention mask and explicit positions differ."""
    from repro.distributed import ctx
    from repro.models import layers as L
    from repro.models import transformer as T

    moe = cfg.n_experts > 0

    def _attn(p, x, seg, pos):
        h = L.apply_norm(cfg, p["norm"], x)
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = L.apply_rope(cfg, q, pos)
        k = L.apply_rope(cfg, k, pos)
        o = packed_attention(q, k, v, seg)
        y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        if cfg.attn_out_bias:
            y = y + p["bo"]
        return x + y, (k, v)

    def forward(params, tokens, seg, pos, last_idx):
        h = T.embed_tokens(cfg, params, tokens)

        def body(hh, lp):
            hh = ctx.constrain(hh)
            x, kv = _attn(lp["attn"], hh, seg, pos)
            x = T._mlp(cfg, lp["mlp_norm"], lp["mlp"], x, moe)
            return x, kv

        h, (k, v) = ctx.lscan(body, h, params["layers"])
        h = L.apply_norm(cfg, params["final_norm"], h)
        h_last = h[0][last_idx][:, None]            # (P, 1, D)
        logits = T.unembed(cfg, params, h_last)     # (P, 1, V)
        return logits[:, 0], k[:, 0], v[:, 0]

    return forward


# ----------------------------------------------------------------------
# The runner
# ----------------------------------------------------------------------

@dataclass
class PackedPrefill:
    """Result of one packed prefill call. ``logits`` rows beyond
    ``len(spans)`` are padding (ignore); ``k``/``v`` are the packed caches —
    slice with ``spans`` or scatter the whole bucket via ``scatter``."""
    logits: jax.Array                    # (max_segments, V)
    k: jax.Array                         # (L, S, Hkv, dh)
    v: jax.Array                         # (L, S, Hkv, dh)
    spans: List[Tuple[int, int]]         # per prompt: (offset, length)
    bucket: int


@dataclass
class PrefillHandoff:
    """Prefill state computed off-engine (a disaggregated prefill socket
    group) and attached to a ``Request`` before it reaches a decode engine:
    the first sampled token plus the prompt's K/V blocks, gathered
    contiguous from the prefill group's paged cache. The decode engine
    adopts it into a slot (``append`` + ``reserve``) instead of running its
    own prefill."""
    first_token: int
    k: np.ndarray                        # (L, S, Hkv, dh)
    v: np.ndarray                        # (L, S, Hkv, dh)

    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes


class PackedPrefillRunner:
    """Bucketed, packed, AOT-compiled prefill for one backbone config.

    One compiled executable per bucket (shared by every expert of the CoE —
    same backbone, §II), plus one donated pool-scatter per bucket.
    ``warmup(params, pool)`` lowers and compiles all of them ahead of time;
    after that a mixed-length burst triggers **zero** XLA compilations
    (every compile goes through ``record_compile``, so the claim is
    enforced by tests/test_prefill.py). Works unchanged with TP-sharded
    params/pools: the forward is plain ``jax.jit``, GSPMD partitions it
    along the captured input shardings exactly like the sequential
    ``prefill_kv`` path.
    """

    def __init__(self, cfg: ModelConfig, *, buckets: Sequence[int],
                 max_segments: int = 8):
        if cfg.family not in ("dense", "moe"):
            raise ValueError("packed prefill supports dense/moe families only")
        if cfg.sliding_window:
            raise ValueError("packed prefill does not support sliding windows")
        if cfg.first_dense_layers:
            raise ValueError("packed prefill: first_dense_layers unsupported")
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError("buckets must be strictly increasing")
        if max_segments < 1:
            raise ValueError("max_segments must be >= 1")
        self.cfg = cfg
        self.buckets = tuple(int(b) for b in buckets)
        self.max_segments = int(max_segments)
        self._fn = packed_prefill_fn(cfg)
        self._fwd: Dict[int, jax.stages.Compiled] = {}
        self._scatter: Dict[int, jax.stages.Compiled] = {}

    # -- compile management -----------------------------------------------
    def _ensure_fwd(self, bucket: int, args):
        if bucket not in self._fwd:
            record_compile("packed_prefill")
            self._fwd[bucket] = jax.jit(self._fn).lower(*args).compile()
        return self._fwd[bucket]

    @staticmethod
    def _scatter_body(pk, pv, kn, vn, rows, offs):
        pk = pk.at[:, rows, offs].set(kn.astype(pk.dtype))
        pv = pv.at[:, rows, offs].set(vn.astype(pv.dtype))
        return pk, pv

    def _ensure_scatter(self, bucket: int, args):
        if bucket not in self._scatter:
            record_compile("packed_scatter")
            self._scatter[bucket] = jax.jit(
                self._scatter_body,
                donate_argnums=(0, 1)).lower(*args).compile()
        return self._scatter[bucket]

    def warmup(self, params, pool) -> None:
        """AOT-compile every bucket's forward and pool-scatter. ``params``
        is any expert of the composition (all share shapes/shardings);
        ``pool`` is the engine's ``PagedKVCache`` — its live arrays pin the
        scatter's input shardings. Executes each forward once on dummy
        tokens (cheap at bucket shapes, and it yields concrete K/V to lower
        the scatter against); the pool itself is never written."""
        scratch = pool.scratch_index if pool.scratch_index is not None else 0
        for b in self.buckets:
            toks = jnp.zeros((1, b), jnp.int32)
            seg = jnp.full((1, b), self.max_segments, jnp.int32)
            pos = jnp.asarray(np.arange(b, dtype=np.int32)[None])
            last = jnp.zeros((self.max_segments,), jnp.int32)
            fwd = self._ensure_fwd(b, (params, toks, seg, pos, last))
            _, k, v = fwd(params, toks, seg, pos, last)
            rows = jnp.full((b,), scratch, jnp.int32)
            offs = jnp.zeros((b,), jnp.int32)
            self._ensure_scatter(b, (pool.k, pool.v, k, v, rows, offs))

    def compiled_forward(self, bucket: int) -> jax.stages.Compiled:
        """The AOT-compiled forward of one warmed (or already run) bucket."""
        return self._fwd[bucket]

    # -- execution --------------------------------------------------------
    def pack(self, prompts: Sequence[np.ndarray]):
        """Build the packed host arrays for one call: tokens, segment ids
        (pad = ``max_segments``), per-segment restarting positions (pad
        positions restart too, so pad rows stay finite), last-token indices
        padded with 0, and the chosen bucket."""
        if not prompts:
            raise ValueError("pack: empty prompt list")
        if len(prompts) > self.max_segments:
            raise ValueError(
                f"pack: {len(prompts)} prompts > max_segments "
                f"{self.max_segments}")
        lens = [len(p) for p in prompts]
        bucket = bucket_for(sum(lens), self.buckets)
        toks = np.zeros((1, bucket), np.int32)
        seg = np.full((1, bucket), self.max_segments, np.int32)
        pos = np.zeros((1, bucket), np.int32)
        last = np.zeros((self.max_segments,), np.int32)
        spans: List[Tuple[int, int]] = []
        off = 0
        for i, p in enumerate(prompts):
            n = len(p)
            toks[0, off:off + n] = p
            seg[0, off:off + n] = i
            pos[0, off:off + n] = np.arange(n)
            last[i] = off + n - 1
            spans.append((off, n))
            off += n
        pos[0, off:] = np.arange(bucket - off)
        return toks, seg, pos, last, spans, bucket

    def __call__(self, params, prompts: Sequence[np.ndarray]) -> PackedPrefill:
        """Run one packed prefill over ``prompts`` (each a 1-D int token
        array). Compiles lazily if the bucket was never warmed."""
        toks, seg, pos, last, spans, bucket = self.pack(prompts)
        args = (params, jnp.asarray(toks), jnp.asarray(seg),
                jnp.asarray(pos), jnp.asarray(last))
        fwd = self._ensure_fwd(bucket, args)
        logits, k, v = fwd(*args)
        return PackedPrefill(logits=logits, k=k, v=v, spans=spans,
                             bucket=bucket)

    def scatter_into(self, pool, res: PackedPrefill, rids: Sequence[int],
                     extra_tokens: Optional[Sequence[int]] = None) -> None:
        """Open each ``rid`` in ``pool``, reserve its span (plus
        ``extra_tokens[i]`` future decode tokens), commit the span length,
        and land the whole packed K/V with ONE donated scatter. Pad
        positions (and nothing else) write the scratch block."""
        if len(rids) != len(res.spans):
            raise ValueError("rids/spans length mismatch")
        scratch = pool.scratch_index if pool.scratch_index is not None else 0
        rows = np.full((res.bucket,), scratch, np.int32)
        offs = np.zeros((res.bucket,), np.int32)
        for j, (rid, (off, n)) in enumerate(zip(rids, res.spans)):
            pool.open(rid)
            pool.reserve(rid, n + (extra_tokens[j] if extra_tokens else 0))
            tbl = np.asarray(pool.table(rid), np.int32)
            t = np.arange(n)
            rows[off:off + n] = tbl[t // pool.block]
            offs[off:off + n] = t % pool.block
            pool.advance(rid, n)
        self.scatter(pool, res, rows, offs)

    def scatter(self, pool, res: PackedPrefill, rows: np.ndarray,
                offs: np.ndarray) -> None:
        """Scatter the packed K/V into the paged pool with donated buffers
        (no copy of the pool). ``rows``/``offs`` are (bucket,) int32 — the
        pool row/offset of every packed position; pad positions must point
        at the scratch block. Reassigns ``pool.k``/``pool.v``."""
        args = (pool.k, pool.v, res.k, res.v,
                jnp.asarray(rows), jnp.asarray(offs))
        fn = self._ensure_scatter(res.bucket, args)
        pool.k, pool.v = fn(*args)
