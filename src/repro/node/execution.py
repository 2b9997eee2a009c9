"""Tensor-parallel expert execution for one socket group (paper §VI-C).

``TPPagedDecodeRunner`` is a drop-in ``PagedDecodeRunner``: the same
``prefill_kv`` / ``extend`` surface the ``ServingEngine`` drives, so
continuous batching, speculative admission logic and the ``HBMWeightCache``
prefetch pipeline all work unchanged per group. The difference is *where*
the math runs:

  * expert weights are sharded over the group mesh's ``model`` axis using
    the same ``distributed/partitioning.py`` rules the training stack uses
    (q/kv heads, FFN hidden, vocab — kv heads replicate when GQA kv < tp);
  * the paged KV pool is sharded over its kv-head dim
    (``partitioning.paged_pool_pspec``) so each socket holds only its KV
    shard;
  * one ``shard_map`` paged-extend step runs the whole decoder on local
    shards with exactly two ``psum`` reductions per layer (attention output
    projection + FFN down projection — the Megatron pattern the paper's
    inter-RDU network serves) plus one for the vocab-sharded embedding
    lookup.

Prefill goes through the inherited jitted forward: with sharded params GSPMD
partitions it along the same axes automatically — only the steady-state
decode step, where collective latency dominates, is hand-mapped.

TP=1 groups skip ``shard_map`` entirely; sharded-on-one-device params pin
the group to its own socket.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import partitioning as part
from repro.obs import trace
from repro.serving.engine import PagedDecodeRunner, ServingEngine
from repro.serving.prefill import record_compile


def _tp_paged_extend(cfg: ModelConfig, tp: int, kv_sharded: bool,
                     vocab_sharded: bool, params, pk, pv, tables, lengths,
                     active, tokens, scratch_row: int):
    """Per-device body of the TP paged-extend step (runs under shard_map).

    Mirrors ``serving.engine._paged_extend`` on local shards: ``params`` are
    the device-local parameter shards, ``pk/pv`` the local KV pool shard
    (kv-head dim), everything else replicated. Activations stay replicated;
    per-layer partial outputs are psum'd over ``'model'``.
    """
    from repro.models import layers as L

    B, g = tokens.shape
    block = pk.shape[2]
    maxb = tables.shape[1]
    S = maxb * block
    Hq, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hq_l = Hq // tp
    Hkv_l = Hkv // tp if kv_sharded else Hkv
    didx = jax.lax.axis_index("model")

    tok_tab = params["embed"]["tok"]
    if vocab_sharded:
        # vocab-sharded embedding: exactly one shard contributes a non-zero
        # row per token, so the psum is a bit-exact select, not a reduction
        Vl = tok_tab.shape[0]
        loc = tokens - didx.astype(jnp.int32) * Vl
        ok = (loc >= 0) & (loc < Vl)
        h = jnp.where(ok[..., None],
                      tok_tab[jnp.clip(loc, 0, Vl - 1)],
                      jnp.zeros((), tok_tab.dtype))
        h = jax.lax.psum(h, "model")
    else:
        h = tok_tab[tokens]

    positions = lengths[:, None] + jnp.arange(g, dtype=jnp.int32)[None]
    blk_idx = jnp.minimum(positions // block, maxb - 1)
    rows = jnp.take_along_axis(tables, blk_idx, axis=1)
    rows = jnp.where(active[:, None], rows, jnp.int32(scratch_row))
    off = positions % block
    kpos = jnp.arange(S, dtype=jnp.int32)
    mask = kpos[None, None, :] <= positions[:, :, None]           # (B,g,S)

    # kv head feeding each LOCAL q head (GQA): global q index -> global kv
    # index, shifted into the local shard when the pool is kv-sharded
    q_glob = didx * Hq_l + jnp.arange(Hq_l)
    kv_glob = q_glob * Hkv // Hq
    kv_idx = kv_glob - didx * Hkv_l if kv_sharded else kv_glob

    def body(hh, xs):
        lp, kp, vp = xs                    # kp (rows, block, Hkv_l, dh)
        p = lp["attn"]
        hn = L.apply_norm(cfg, p["norm"], hh)
        q = jnp.einsum("bsd,dhk->bshk", hn, p["wq"])              # local heads
        k = jnp.einsum("bsd,dhk->bshk", hn, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", hn, p["wv"])
        if cfg.qkv_bias:                   # head-sharded biases: local adds
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = L.apply_rope(cfg, q, positions)
        k = L.apply_rope(cfg, k, positions)
        kp = kp.at[rows, off].set(k.astype(kp.dtype))
        vp = vp.at[rows, off].set(v.astype(vp.dtype))
        kc = kp[tables].reshape(B, S, *kp.shape[2:])              # (B,S,Hkv_l,dh)
        vc = vp[tables].reshape(B, S, *vp.shape[2:])
        k_sel = kc[:, :, kv_idx]                                  # (B,S,Hq_l,dh)
        v_sel = vc[:, :, kv_idx]
        s = jnp.einsum("bqhd,bshd->bhqs", q, k_sel,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        s = jnp.where(mask[:, None], s, -jnp.inf)
        pa = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqs,bshd->bqhd", pa.astype(v_sel.dtype), v_sel,
                       preferred_element_type=jnp.float32)
        o = o.astype(hh.dtype)
        y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])               # partial
        y = jax.lax.psum(y, "model")                              # reduce #1
        if cfg.attn_out_bias:
            y = y + p["bo"]                # replicated bias: add once, post-psum
        hh = hh + y

        mp = lp["mlp"]
        hn = L.apply_norm(cfg, lp["mlp_norm"], hh)
        if cfg.act in ("swiglu", "geglu"):
            gate = hn @ mp["wi_gate"]
            up = hn @ mp["wi_up"]
            if cfg.mlp_bias:
                gate = gate + mp["bi_gate"]
                up = up + mp["bi_up"]
            hf = L._act(cfg, gate) * up
        else:
            hf = hn @ mp["wi"]
            if cfg.mlp_bias:
                hf = hf + mp["bi"]
            hf = L._act(cfg, hf)
        y = hf @ mp["wo"]                                         # partial
        y = jax.lax.psum(y, "model")                              # reduce #2
        if cfg.mlp_bias:
            y = y + mp["bo"]
        hh = hh + y
        return hh, (kp, vp)

    h, (pk, pv) = jax.lax.scan(body, h, (params["layers"], pk, pv))
    h = L.apply_norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, tok_tab)
    else:
        logits = h @ params["lm_head"]
    return logits, pk, pv                  # logits vocab-local when sharded


def _tp_fused_paged_extend(cfg: ModelConfig, tp: int, vocab_sharded: bool,
                           params, pk, pv, tables, lengths, active, tokens,
                           scratch_row: int, interpret=None):
    """Fused-backend per-device body for the single-token TP extend step.

    Requires a kv-sharded pool (``Hkv % tp == 0``): then each device's local
    q heads map contiguously onto its local kv heads with the global GQA
    group size, so the Pallas prologue + paged flash-decode run unchanged on
    local head counts. Only the two Megatron reductions (attention out-proj,
    FFN down-proj) and the K/V scatter stay in XLA — the FFN runs the fused
    SwiGLU kernel in residual-free form so its partial output can be psum'd
    before the residual add.
    """
    from repro.kernels.flash_attention.ops import decode_paged
    from repro.kernels.fused_decode.kernel import ffn_swiglu, qkv_rope_paged
    from repro.kernels.runtime import resolve_interpret
    from repro.models import layers as L

    B, g = tokens.shape
    assert g == 1
    block = pk.shape[2]
    maxb = tables.shape[1]
    it = resolve_interpret(interpret)
    didx = jax.lax.axis_index("model")

    tok_tab = params["embed"]["tok"]
    if vocab_sharded:
        # bit-exact psum-select (see _tp_paged_extend)
        Vl = tok_tab.shape[0]
        loc = tokens - didx.astype(jnp.int32) * Vl
        ok = (loc >= 0) & (loc < Vl)
        h = jnp.where(ok[..., None],
                      tok_tab[jnp.clip(loc, 0, Vl - 1)],
                      jnp.zeros((), tok_tab.dtype))
        h = jax.lax.psum(h, "model")
    else:
        h = tok_tab[tokens]
    h = h[:, 0]                                                   # (B, D)

    pos = lengths
    blk_idx = jnp.minimum(pos // block, maxb - 1)
    rows = jnp.take_along_axis(tables, blk_idx[:, None], axis=1)[:, 0]
    rows = jnp.where(active, rows, jnp.int32(scratch_row))
    off = pos % block
    len1 = lengths + 1

    def body(hh, xs):
        lp, kp, vp = xs                    # kp (rows, block, Hkv_l, dh)
        p = lp["attn"]
        q, k, v = qkv_rope_paged(hh, p["norm"]["scale"], p["wq"], p["wk"],
                                 p["wv"], pos, theta=cfg.rope_theta,
                                 interpret=it)
        kp = kp.at[rows, off].set(k.astype(kp.dtype))
        vp = vp.at[rows, off].set(v.astype(vp.dtype))
        o = decode_paged(q, kp, vp, tables, len1, interpret=it)   # (B,Hq_l,dh)
        y = jnp.einsum("bhk,hkd->bd", o.astype(hh.dtype), p["wo"])  # partial
        hh = hh + jax.lax.psum(y, "model")                        # reduce #1
        mp = lp["mlp"]
        y = ffn_swiglu(hh, lp["mlp_norm"]["scale"], mp["wi_gate"],
                       mp["wi_up"], mp["wo"], residual=False,
                       interpret=it)                              # partial
        hh = hh + jax.lax.psum(y, "model")                        # reduce #2
        return hh, (kp, vp)

    h, (pk, pv) = jax.lax.scan(body, h, (params["layers"], pk, pv))
    h = L.apply_norm(cfg, params["final_norm"], h)[:, None]       # (B,1,D)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, tok_tab)
    else:
        logits = h @ params["lm_head"]
    return logits, pk, pv                  # logits vocab-local when sharded


class TPPagedDecodeRunner(PagedDecodeRunner):
    """Paged prefill/extend for one socket group's mesh.

    Requires q heads and the FFN hidden dim divisible by the TP degree (use
    ``configs.pad_for_tp``); kv heads and vocab shard when divisible and
    replicate otherwise (the same decisions ``partitioning.leaf_pspec``
    encodes — the in_specs are read off the pspec tree, never re-derived).
    """

    def __init__(self, cfg: ModelConfig, scratch_row: int, mesh: Mesh,
                 backend: str = "xla"):
        super().__init__(cfg, scratch_row, backend=backend)
        if "model" not in mesh.axis_names:
            raise ValueError("socket-group mesh must carry a 'model' axis")
        from repro.models import get_model
        self.mesh = mesh
        self.tp = int(mesh.shape["model"])
        specs = get_model(cfg).param_specs()
        self.param_pspecs = part.param_pspecs(specs, mesh)
        self.param_shardings = part.param_shardings(specs, mesh)
        self.pool_pspec = part.paged_pool_pspec(cfg, mesh)
        if self.tp == 1:
            self.kv_sharded = self.vocab_sharded = False
            return
        if cfg.n_experts > 0:
            raise ValueError("TP paged extend supports dense FFN only")
        if cfg.n_heads % cfg.n_kv_heads:
            raise ValueError("TP paged extend needs n_heads % n_kv_heads == 0")
        attn = self.param_pspecs["layers"]["attn"]
        mlp = self.param_pspecs["layers"]["mlp"]
        if attn["wq"][2] != "model" or mlp["wo"][1] != "model":
            raise ValueError(
                f"n_heads={cfg.n_heads} / d_ff={cfg.d_ff} do not shard over "
                f"tp={self.tp} — pad the config with configs.pad_for_tp")
        self.kv_sharded = attn["wk"][2] == "model"
        self.vocab_sharded = (
            self.param_pspecs["embed"]["tok"][0] == "model")
        if self.backend.name == "fused" and not self.kv_sharded:
            raise ValueError(
                "backend='fused' TP extend needs a kv-sharded pool "
                f"(n_kv_heads={cfg.n_kv_heads} does not shard over "
                f"tp={self.tp}) — use backend='xla' for this group shape")

    def place_params(self, host_tree):
        """Host pytree -> TP-sharded device pytree on the group mesh (what
        the group's ``HBMWeightCache`` uses as its ``sharding=``)."""
        return jax.device_put(host_tree, self.param_shardings)

    def _tp_body(self, g: int):
        """Per-device extend body for one group size: the fused Pallas body
        for single-token steps on the fused backend, else the XLA body
        (multi-token verify steps always take the XLA body, mirroring the
        single-device ``FusedPagedBackend`` dispatch)."""
        cfg, scratch = self.cfg, self.scratch_row
        tp, kvs, vs = self.tp, self.kv_sharded, self.vocab_sharded
        if self.backend.name == "fused" and g == 1:
            it = self.backend.interpret
            return lambda p, k, v, tb, ln, ac, tk: _tp_fused_paged_extend(
                cfg, tp, vs, p, k, v, tb, ln, ac, tk, scratch, interpret=it)
        return lambda p, k, v, tb, ln, ac, tk: _tp_paged_extend(
            cfg, tp, kvs, vs, p, k, v, tb, ln, ac, tk, scratch)

    def extend(self, params, pk, pv, tables, lengths, active, tokens):
        if self.tp == 1:
            return super().extend(params, pk, pv, tables, lengths, active,
                                  tokens)
        key = tokens.shape
        if key not in self._extend:
            record_compile("tp_extend")
            logits_spec = P(None, None, "model") if self.vocab_sharded else P()
            mapped = jax.shard_map(
                self._tp_body(key[1]),
                mesh=self.mesh,
                in_specs=(self.param_pspecs, self.pool_pspec, self.pool_pspec,
                          P(), P(), P(), P()),
                out_specs=(logits_spec, self.pool_pspec, self.pool_pspec),
                check_vma=False)
            self._extend[key] = jax.jit(mapped, donate_argnums=(1, 2))
        args = (params, pk, pv, jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(active), jnp.asarray(tokens))
        if key not in self._abstract:
            self._abstract[key] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.asarray(x).dtype), args)
        self._last_key = key
        with trace.span("decode_kernel", cat="kernel",
                        backend=self.backend.name, tp=self.tp,
                        batch=key[0], g=key[1]):
            return self._extend[key](*args)


class PrefillWorker:
    """A socket group dedicated to prefill (disaggregated serving).

    Prefill is compute-bound, decode bandwidth-bound — colocating them makes
    every admit a head-of-line stall for the decode batch. A node in
    disaggregated mode (``RDUNode(prefill_groups=N)``) dedicates socket
    groups to prefill: each worker owns its own ``CompositionOfExperts``
    cache over the node's shared store, a ``PackedPrefillRunner`` (bucketed
    AOT forwards, TP via GSPMD on the group mesh), and a small TP-sharded
    paged pool that holds K/V only between the packed scatter and the
    block handoff. ``step()`` packs the FIFO queue's same-expert requests
    into one bucketed call, then gathers each request's blocks out of the
    group cache and attaches them as a ``PrefillHandoff`` — the node
    forwards the request to a decode group, whose engine adopts the blocks
    into its own cache without re-running the forward.
    """

    def __init__(self, group, coe, cfg: ModelConfig, *,
                 max_len: int = 4096, block_size: int = 16,
                 n_pack: int = 8, buckets=None, kv_dtype=jnp.bfloat16,
                 registry=None, labels=None):
        from repro.obs.metrics import MetricsRegistry
        from repro.serving.kvcache import PagedKVCache
        from repro.serving.prefill import PackedPrefillRunner, default_buckets

        self.group = group
        self.coe = coe
        self.cfg = cfg
        self.block = block_size
        self.registry = registry if registry is not None else MetricsRegistry()
        self.labels = dict(labels or {})
        buckets = tuple(buckets) if buckets else default_buckets(max_len)
        self.runner = PackedPrefillRunner(cfg, buckets=buckets,
                                          max_segments=n_pack)
        # staging pool: one packed bucket in flight at a time, so the cap
        # is the largest bucket's blocks + per-request rounding + scratch
        n_blocks = -(-buckets[-1] // block_size) + n_pack
        self.pool = PagedKVCache(n_blocks, block_size, cfg.n_layers,
                                 cfg.n_kv_heads, cfg.head_dim, kv_dtype,
                                 scratch=True, registry=self.registry,
                                 labels=self.labels)
        # TP placement mirrors make_group_engine: params shard over the
        # group mesh via the partitioning rules, the staging pool over its
        # kv-head dim; the packed forward is plain jit, GSPMD does the rest
        from repro.models import get_model
        specs = get_model(cfg).param_specs()
        self.param_shardings = part.param_shardings(specs, mesh=group.mesh)
        sh = NamedSharding(group.mesh, part.paged_pool_pspec(cfg, group.mesh))
        self.pool.k = jax.device_put(self.pool.k, sh)
        self.pool.v = jax.device_put(self.pool.v, sh)
        coe.cache.sharding = self.param_shardings
        self.queue = []
        self.prefilled = 0
        self._ttft_hist = self.registry.histogram("serve.ttft_s",
                                                  labels=self.labels)
        self._handoff_bytes = self.registry.counter("node.kv_handoff_bytes",
                                                    labels=self.labels)
        self._handoffs = self.registry.counter("node.kv_handoffs",
                                               labels=self.labels)

    @property
    def load(self) -> int:
        return len(self.queue)

    @property
    def has_work(self) -> bool:
        return bool(self.queue)

    def submit(self, req):
        if len(req.tokens) > self.runner.buckets[-1]:
            raise ValueError(
                f"request {req.rid}: {len(req.tokens)} prompt tokens exceed "
                f"the prefill group's largest bucket "
                f"{self.runner.buckets[-1]}")
        self.queue.append(req)

    def warmup(self, expert=None):
        """AOT-compile every bucket forward + scatter against this group's
        sharded params/pool."""
        names = self.coe.expert_names()
        if not names:
            raise RuntimeError("prefill worker warmup: no experts registered")
        params = self.coe.cache.activate(expert or names[0])
        self.runner.warmup(params, self.pool)

    def step(self):
        """Prefill one packed batch: the queue head's expert, same-expert
        requests packed FIFO until the largest bucket (or ``n_pack``) fills.
        Returns the completed requests, each carrying a ``PrefillHandoff``.
        """
        import time

        from repro.serving.prefill import PrefillHandoff

        if not self.queue:
            return []
        expert = self.queue[0].expert
        cap = self.runner.buckets[-1]
        picked, rest, total = [], [], 0
        for r in self.queue:
            n = len(r.tokens)
            if (r.expert == expert and len(picked) < self.runner.max_segments
                    and total + n <= cap):
                picked.append(r)
                total += n
            else:
                rest.append(r)
        self.queue = rest
        params = self.coe.cache.activate(expert)
        with trace.span("prefill", cat="node", group=self.group.gid,
                        expert=expert, prompt_tokens=total,
                        **{"prefill.packed": len(picked)}) as sp:
            res = self.runner(params, [r.tokens for r in picked])
            sp.add(**{"prefill.bucket": res.bucket})
            firsts = np.asarray(
                jnp.argmax(res.logits[:len(picked)], axis=-1), np.int32)
            self.runner.scatter_into(self.pool, res,
                                     [r.rid for r in picked])
        out = []
        for i, r in enumerate(picked):
            with trace.span("kv_handoff", cat="node", group=self.group.gid,
                            request_id=r.rid):
                k, v = self.pool.gather(r.rid)
                # the handoff crosses the inter-socket fabric: materialize
                # on host, then release the staging blocks
                hk, hv = np.asarray(k), np.asarray(v)
                self.pool.free(r.rid)
            r.handoff = PrefillHandoff(first_token=int(firsts[i]),
                                       k=hk, v=hv)
            now = time.perf_counter()
            r.prefill_done_s = now
            r.first_token_s = now
            self._ttft_hist.observe(now - r.arrival_s)
            self._handoff_bytes.inc(hk.nbytes + hv.nbytes)
            self._handoffs.inc()
            self.prefilled += 1
            out.append(r)
        return out


def make_group_engine(coe, cfg: ModelConfig, mesh: Mesh,
                      **engine_kwargs) -> ServingEngine:
    """A ``ServingEngine`` whose runner executes tensor-parallel on one
    socket group's mesh and whose paged KV pool lives sharded on that
    group's devices (per-socket KV shards)."""
    eng = ServingEngine(
        coe, cfg,
        runner_factory=lambda c, s, **kw: TPPagedDecodeRunner(c, s, mesh,
                                                              **kw),
        **engine_kwargs)
    sh = NamedSharding(mesh, eng.runner.pool_pspec)
    eng.pool.k = jax.device_put(eng.pool.k, sh)
    eng.pool.v = jax.device_put(eng.pool.v, sh)
    # the group's weight cache must install TP-sharded params on this mesh
    coe.cache.sharding = eng.runner.param_shardings
    return eng
