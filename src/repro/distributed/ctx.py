"""Activation-sharding context: lets step builders impose a sharding
constraint on the inter-layer residual stream without threading mesh details
through every model family.

Megatron-SP analogue: during training the residual (B, S, D) is constrained
to shard S over 'model' between layers, so the per-layer scan carries saved
for backward shrink by the TP degree; GSPMD inserts the all-gather /
reduce-scatter pairs around attention/MLP automatically.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import PartitionSpec

_ACTIVATION_PSPEC: Optional[PartitionSpec] = None
_NAMED: dict = {}


@contextlib.contextmanager
def activation_sharding(pspec: Optional[PartitionSpec]):
    global _ACTIVATION_PSPEC
    prev = _ACTIVATION_PSPEC
    _ACTIVATION_PSPEC = pspec
    try:
        yield
    finally:
        _ACTIVATION_PSPEC = prev


@contextlib.contextmanager
def named_shardings(**pspecs):
    """Named sharding constraints for family-internal tensors (e.g. the
    per-layer KV cache slice inside the decode scan — pinning it stops GSPMD
    from re-sharding the carry and all-gathering the whole cache)."""
    global _NAMED
    prev = dict(_NAMED)
    _NAMED.update(pspecs)
    try:
        yield
    finally:
        _NAMED = prev


def constrain(h):
    """Apply the active activation constraint to a (B, S, D) residual."""
    if _ACTIVATION_PSPEC is None:
        return h
    return jax.lax.with_sharding_constraint(h, _ACTIVATION_PSPEC)


def constrain_named(name: str, x):
    p = _NAMED.get(name)
    if p is None:
        return x
    return jax.lax.with_sharding_constraint(x, p)


# ----------------------------------------------------------------------
# Layer-scan unroll control.
#
# XLA's cost analysis counts a while-loop body ONCE (no trip-count
# multiplication), so the roofline extractor compiles reduced-depth models
# with fully-unrolled layer scans to recover exact per-layer costs
# (launch/loopfix.py). Models route their layer/group scans through
# ``lscan`` so that unrolling can be switched on from outside.
# ----------------------------------------------------------------------
_LAYER_UNROLL = False


@contextlib.contextmanager
def unrolled_layer_scans():
    global _LAYER_UNROLL
    prev = _LAYER_UNROLL
    _LAYER_UNROLL = True
    try:
        yield
    finally:
        _LAYER_UNROLL = prev


def lscan(body, init, xs, length=None):
    """Layer scan: jax.lax.scan that fully unrolls under
    ``unrolled_layer_scans()`` (used by the roofline corrector)."""
    if length is None:
        length = jax.tree.leaves(xs)[0].shape[0]
    unroll = length if _LAYER_UNROLL else 1
    return jax.lax.scan(body, init, xs, length=length, unroll=unroll)


# ----------------------------------------------------------------------
# Perf options (the §Perf hillclimb knobs). Defaults = paper-faithful
# baseline; variants are switched per-compile by the hillclimb runner.
# ----------------------------------------------------------------------
import dataclasses as _dc


@_dc.dataclass(frozen=True)
class PerfOptions:
    # decode: shard the KV-cache sequence dim over 'model' when kv-heads
    # can't shard (flash-decode context parallelism; GQA kv<16 archs)
    cache_seq_shard: bool = False
    # train: Megatron-SP sequence-sharded residuals between layers
    activation_sp: bool = True
    # MoE: pin dispatch buffers so EP resolves to all-to-all, not gathers
    moe_dispatch_constraint: bool = False
    # MoE: capacity factor override (0 = keep config)
    capacity_factor: float = 0.0
    # train: chunked-vocab cross entropy (never materialize full logits)
    chunked_loss: bool = False
    # MoE: shard_map-local EP dispatch (no global sort/scatter collectives)
    moe_ep_local: bool = False
    # loss: select gold logits via iota-compare (shardable over vocab)
    # instead of take_along_axis (which gathers the sharded logits)
    onehot_xent: bool = False
    # decode: thread the full KV cache through the layer loop as a carry
    # (in-place slice updates) instead of scan xs/ys reassembly
    decode_cache_carry: bool = False
    # xlstm: bf16 chunkwise mLSTM compute (f32 gates/state only) — halves
    # the TP all-reduce payloads
    mlstm_bf16: bool = False


_PERF = PerfOptions()


@contextlib.contextmanager
def perf_options(opts: "PerfOptions"):
    global _PERF
    prev = _PERF
    _PERF = opts
    try:
        yield
    finally:
        _PERF = prev


def perf() -> "PerfOptions":
    return _PERF


_MESH = None


@contextlib.contextmanager
def mesh_ctx(mesh):
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield
    finally:
        _MESH = prev


def current_mesh():
    return _MESH
