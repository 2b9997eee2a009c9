"""Float32 reference logits, and the one logit comparison of the repo.

The reference is the model's own full forward (``models.transformer``: no
KV cache, no Pallas kernels) on the same weights cast to float32, with every
matmul at ``precision="highest"``. Serving paths are compared with it on
logits, never on tokens: a greedy token flips on a near-tie that says
nothing about correctness.

``max_logit_error`` measures the largest absolute difference in units of
the reference logits' RMS, so one tolerance holds at any logit scale:

* ``F32_TOL``: two float32 programs (different fusion, packing or
  sharding) differ only in summation order, about 1e-7 relative per op.
* ``BF16_TOL``: the serving path keeps weights, KV and the residual stream
  in bfloat16 (unit roundoff 2**-9, about 2e-3). Rounding the residual and
  every projection output across a few layers compounds to a few
  percent of the logit RMS at worst; 0.1 leaves room for that and still
  fails a wrong mask, position, head mapping or cache row, each of which
  moves logits by O(1) RMS.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

F32_TOL = 1e-5
BF16_TOL = 0.1


def max_logit_error(got, ref) -> float:
    """max |got - ref| over every entry, over the RMS of ``ref``."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    return float(np.max(np.abs(got - ref)) / np.sqrt(np.mean(ref * ref)))


def assert_logits_close(got, ref, tol: float, what: str = "logits") -> float:
    """Raise unless ``max_logit_error(got, ref) <= tol``; returns the error."""
    err = max_logit_error(got, ref)
    if not err <= tol:
        raise AssertionError(
            f"{what}: max logit error {err:.4g} x RMS exceeds {tol}")
    return err


@functools.lru_cache(maxsize=None)
def _forward_f32(cfg: ModelConfig):
    from repro.models import transformer as T

    def fwd(params, tokens):
        p32 = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        with jax.default_matmul_precision("highest"):
            return T.forward(cfg, p32, {"tokens": tokens})[0]
    return jax.jit(fwd)


def reference_logits(cfg: ModelConfig, params, tokens,
                     pad_to: int = 256) -> np.ndarray:
    """(S,) token ids -> (S, V) float32 logits at every position. The input
    is padded at the end to a multiple of ``pad_to`` so prompts of nearby
    lengths share one compile; causal attention keeps pad tokens out of
    every real position."""
    tokens = np.asarray(tokens, np.int32)
    S = len(tokens)
    padded = np.zeros((-(-S // pad_to) * pad_to,), np.int32)
    padded[:S] = tokens
    out = _forward_f32(cfg)(params, jnp.asarray(padded[None]))
    return np.asarray(out[:S], np.float32)
