"""Jit'd wrappers for flash attention kernels (BSHD layout in/out)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_prefill, flash_decode,
                                                  flash_decode_paged)
from repro.kernels.runtime import resolve_interpret as _interp


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128,
              interpret=None):
    """q (B,S,Hq,dh), k/v (B,S,Hkv,dh) -> (B,S,Hq,dh)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_prefill(qt, kt, vt, causal=causal, window=window,
                      block_q=block_q, block_k=block_k,
                      interpret=_interp(interpret))
    return o.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode(q, k_cache, v_cache, length, *, block_k=512, interpret=None):
    """q (B,Hq,dh), caches (B,S,Hkv,dh), length scalar -> (B,Hq,dh).

    GQA: kv heads are mapped over with their q-head group.
    """
    B, Hq, dh = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, dh).transpose(1, 0, 2, 3)        # (Hkv,B,G,dh)
    kg = k_cache.transpose(2, 0, 1, 3)[:, :, :, None]          # (Hkv,B,S,1,dh)
    vg = v_cache.transpose(2, 0, 1, 3)[:, :, :, None]
    lv = jnp.asarray(length, jnp.int32).reshape(1)
    fn = lambda qq, kk, vv: flash_decode(qq, kk, vv, lv, block_k=block_k,
                                         interpret=_interp(interpret))
    o = jax.vmap(fn)(qg, kg, vg)                               # (Hkv,B,G,dh)
    return o.transpose(1, 0, 2, 3).reshape(B, Hq, dh)


@partial(jax.jit, static_argnames=("interpret",))
def decode_paged(q, k_pool, v_pool, tables, len1, *, interpret=None):
    """Paged-native GQA decode: q (B,Hq,dh) against the block pool.

    k_pool/v_pool (rows, block, Hkv, dh) — ``PagedKVCache`` arrays;
    tables (B, maxb) int32 padded with the scratch row; len1 (B,) int32 =
    per-lane valid positions (length + 1 after this step's scatter).
    Returns (B, Hq, dh). Queries are grouped (B, G, Hkv, dh) so each pool
    block's tile serves every q head from one gather.
    """
    B, Hq, dh = q.shape
    Hkv = k_pool.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, dh).transpose(0, 2, 1, 3)
    o = flash_decode_paged(qg, k_pool, v_pool, tables, len1,
                           interpret=_interp(interpret))
    return o.transpose(0, 2, 1, 3).reshape(B, Hq, dh)
