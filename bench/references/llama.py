"""Plain float32 reference of a Llama-architecture decoder.

RMSNorm (scale times normalised x), grouped-query attention with full
rotate-half RoPE and a causal mask, SwiGLU FFN, no biases, an untied output
head. Every matmul runs in float32 at ``precision="highest"``. No KV cache,
no kernels, no batching: one sequence at a time, the layers under one scan.
It imports nothing of the program under test and reads only the weights the
benchmark made (``benchlib.weights``: layers stacked on a leading axis).

``quant="fp8"`` is the control of the output check: every projection is
rounded to float8 e4m3 with one scale per output channel before the same
float32 computation, the precision a later change might be tempted to serve
in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _fp8(w, axis):
    """Round ``w`` to float8 e4m3 with one scale per slice along the
    contracted ``axis`` (so per output channel), back in float32."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, dh) -> rotate-half RoPE at positions ``pos`` (S,)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _forward(m_items: tuple, quant: str):
    m = dict(m_items)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    q8 = (lambda w, axis: _fp8(w, axis)) if quant == "fp8" else \
        (lambda w, axis: w)
    f32 = lambda x: x.astype(jnp.float32)

    def layer(h, lw):
        S = h.shape[0]
        a, mlp = lw["attn"], lw["mlp"]
        pos = jnp.arange(S)
        x = _rms(h, f32(a["norm"]["scale"]), eps)
        q = jnp.einsum("sd,dhk->shk", x, q8(f32(a["wq"]), 0), precision=HI)
        k = jnp.einsum("sd,dhk->shk", x, q8(f32(a["wk"]), 0), precision=HI)
        v = jnp.einsum("sd,dhk->shk", x, q8(f32(a["wv"]), 0), precision=HI)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        Hq, dh = q.shape[1], q.shape[2]
        Hkv = k.shape[1]
        qg = q.reshape(S, Hkv, Hq // Hkv, dh)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) / math.sqrt(dh)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI).reshape(S, Hq, dh)
        h = h + jnp.einsum("shk,hkd->sd", o, q8(f32(a["wo"]), (0, 1)),
                           precision=HI)
        x = _rms(h, f32(lw["mlp_norm"]["scale"]), eps)
        g = jnp.dot(x, q8(f32(mlp["wi_gate"]), 0), precision=HI)
        u = jnp.dot(x, q8(f32(mlp["wi_up"]), 0), precision=HI)
        h = h + jnp.dot(jax.nn.silu(g) * u, q8(f32(mlp["wo"]), 0),
                        precision=HI)
        return h, None

    def logits(params, tokens):
        h = f32(params["embed"]["tok"][tokens])
        h, _ = jax.lax.scan(layer, h, params["layers"])
        h = _rms(h, f32(params["final_norm"]["scale"]), eps)
        return jnp.dot(h, q8(f32(params["lm_head"]), 0), precision=HI)

    @jax.jit
    def scored(params, tokens, served):
        """Per position: reference's best logit, logit std, and the logit
        of ``served`` (S,) at that position."""
        lg = logits(params, tokens)
        got = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
        return jnp.max(lg, -1), jnp.std(lg, -1), got

    @jax.jit
    def top(params, tokens):
        return jnp.argmax(logits(params, tokens), -1).astype(jnp.int32)

    return scored, top


def _model_key(m: dict) -> tuple:
    keys = ("rms_norm_eps", "rope_theta")
    return tuple((k, m[k]) for k in keys)


def gaps(m: dict, params, tokens: np.ndarray, start: int, served: np.ndarray,
         pad_to: int) -> np.ndarray:
    """Gap of each served token below the reference's best logit, in units
    of that position's logit std. ``tokens`` (T,) is the prompt and the
    served tokens but the last; ``served[i]`` was served after position
    ``start + i``. The sequence is padded at the end to ``pad_to`` so every
    request shares one compile; the causal mask keeps the pad out."""
    scored, _ = _forward(_model_key(m), "none")
    toks, idx = _padded(tokens, start, served, pad_to)
    best, std, got = (np.asarray(x) for x in scored(params, toks, idx))
    sl = slice(start, start + len(served))
    return (best[sl] - got[sl]) / std[sl]


def control_gaps(m: dict, params, tokens: np.ndarray, start: int, n: int,
                 pad_to: int) -> np.ndarray:
    """The control: at the same positions, the gap of the token that the
    fp8-weight reference puts first, read against the float32 reference."""
    _, top = _forward(_model_key(m), "fp8")
    toks, _ = _padded(tokens, start, np.zeros(n, np.int32), pad_to)
    ctl = np.asarray(top(params, toks))[start:start + n]
    return gaps(m, params, tokens, start, ctl, pad_to)


def _padded(tokens, start, served, pad_to):
    T = len(tokens)
    if T > pad_to:
        raise ValueError(f"sequence of {T} tokens exceeds pad_to {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:T] = tokens
    idx = np.zeros((pad_to,), np.int32)
    idx[start:start + len(served)] = served
    return jnp.asarray(toks), jnp.asarray(idx)
