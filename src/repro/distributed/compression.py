"""Int8 error-feedback gradient compression for data-parallel all-reduce.

Distributed-optimization trick for 1000+ node scale: DP gradient all-reduce
bytes drop 4x (f32 -> int8 + per-tensor scale) with an error-feedback
residual carried in the optimizer state so the quantization error is
re-injected next step (convergence-safe in practice; see DESIGN.md §5).

Implemented with shard_map + explicit psum so the wire format is actually
int8->int32 (GSPMD's implicit reduction would promote to f32). Off by
default; enabled with TrainConfig.grad_compression='int8'.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum_int8(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Inside shard_map: all-reduce-mean x over ``axis_name`` in int8.

    Two-phase: (1) pmax a shared per-tensor scale (4 bytes on the wire) so
    every replica quantizes on the same grid; (2) psum the int8 payload as
    int32 (no overflow for <=2^23 replicas). Quantization error is bounded
    by one grid step of the global max.
    """
    smax = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_name) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / smax), -127, 127).astype(jnp.int8)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return qsum.astype(jnp.float32) * smax / n


def make_compressed_allreduce(mesh: Mesh, axis: str = "data"):
    """Returns fn(grads_pytree) -> mean-reduced grads over the data axis,
    with int8 wire format. Grads must be replicated over `axis` per shard
    (the usual per-replica local gradients)."""

    def _one(g):
        def body(gl):
            return compressed_psum_int8(gl, axis)
        return jax.shard_map(
            body, mesh=mesh, in_specs=P(axis), out_specs=P(),
            check_vma=False,
        )(g)

    def reduce_tree(grads):
        return jax.tree.map(_one, grads)

    return reduce_tree


def error_feedback_update(grad, residual):
    """Apply error feedback: compress(grad + residual); new residual is the
    quantization error. Returns (compressed_value, new_residual)."""
    target = grad.astype(jnp.float32) + residual
    q, scale = quantize_int8(target)
    deq = dequantize_int8(q, scale)
    return deq, target - deq
