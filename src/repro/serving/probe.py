"""Logits of the engine's own compiled paths, for one prompt.

The engine emits tokens, not logits. ``probe_logits`` drives an engine's own
prefill runner and decode runner on the engine's own paged KV pool — the
programs that serve traffic — for one prompt plus a few greedy decode
steps, and returns their logits for comparison with
``models.reference.reference_logits`` on the same weights.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np


def probe_logits(engine, params, prompt, n_decode: int = 4,
                 rid: int = -1) -> Tuple[np.ndarray, np.ndarray]:
    """Prefill ``prompt`` (S,) into lane 0 of ``engine`` under ``params``,
    then run ``n_decode`` single-token extend steps feeding back each
    argmax. Returns ``(logits (1 + n_decode, V) float32, fed (n_decode,))``:
    row 0 predicts position S, row i predicts S + i after ``fed[i-1]``.
    The engine must be idle; ``rid`` must not name a live request. The
    pool is left as found."""
    pool, B = engine.pool, engine.n_slots
    prompt = np.asarray(prompt, np.int32)
    if engine.prefill_runner is not None:
        res = engine.prefill_runner(params, [prompt])
        engine.prefill_runner.scatter_into(pool, res, [rid],
                                           extra_tokens=[n_decode])
        first = res.logits[0]
    else:
        first, k, v = engine.runner.prefill_kv(params,
                                               jnp.asarray(prompt[None]))
        pool.open(rid)
        pool.append(rid, k, v)
        pool.reserve(rid, n_decode)
    out = [np.asarray(first, np.float32)]
    fed = []
    tables = np.full((B, engine.max_blocks), pool.scratch_index, np.int32)
    active = np.zeros((B,), bool)
    active[0] = True
    for _ in range(n_decode):
        fed.append(int(np.argmax(out[-1])))
        tables[0] = pool.padded_table(rid, engine.max_blocks)
        lengths = np.zeros((B,), np.int32)
        lengths[0] = pool.length(rid)
        toks = np.zeros((B, 1), np.int32)
        toks[0, 0] = fed[-1]
        logits, pool.k, pool.v = engine.runner.extend(
            params, pool.k, pool.v, tables, lengths, active, toks)
        pool.advance(rid, 1)
        out.append(np.asarray(logits[0, -1], np.float32))
    pool.free(rid)
    return np.stack(out), np.asarray(fed, np.int32)
