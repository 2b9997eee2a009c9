"""Weights made from ``--seed`` by the benchmark itself, in bfloat16.

The tree, each leaf's shape and its init std (None: a norm scale of ones)
come from the architecture's ``shapes(m)``, in the layout the serving
program takes. One jitted call makes a whole base model on the device; one
more makes every expert's own ``EXPERT_LEAF`` (the architecture's too).
Leaf ``i`` of the base, in tree order, draws from the ``i``-th split of
stream 0 of the seed; expert ``i`` from stream ``i + 1``. Experts share
every other leaf of the base on the host, so building 8 experts copies the
base to the host once; each expert still moves its whole tree host->HBM
when it is switched in, because the host store hands the program whole
trees.
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import BENCH_DIR, spec


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _draw(key, shape, std):
    if std is None:
        return jnp.ones(shape, jnp.bfloat16)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def make_base(m: dict, seed: int, bench: Path = BENCH_DIR):
    """Device tree of the base model for ``seed``, made in one jitted call."""
    leaves, treedef = jax.tree.flatten(spec.arch(m, bench).shapes(m),
                                       is_leaf=_is_spec)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_draw(k, s, std) for k, (s, std) in zip(keys, leaves)])

    return build(_key(seed, 0))


def make_expert_leaves(m: dict, seed: int, n: int,
                       bench: Path = BENCH_DIR):
    """Device array ``(n, ...)`` of each expert's own ``EXPERT_LEAF``, made
    in one jitted call; expert ``i`` draws from stream ``i + 1``."""
    arch = spec.arch(m, bench)
    s, std = _get(arch.shapes(m), arch.EXPERT_LEAF)
    keys = jnp.stack([_key(seed, i + 1) for i in range(n)])
    return jax.jit(jax.vmap(lambda k: _draw(k, s, std)))(keys)


def _key(seed: int, stream: int):
    ss = np.random.SeedSequence([seed % 2**32, seed // 2**32, stream])
    return jax.random.PRNGKey(int(ss.generate_state(1, np.uint32)[0]))


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _with(tree, path, value):
    if not path:
        return value
    out = dict(tree)
    out[path[0]] = _with(tree[path[0]], path[1:], value)
    return out


def build_experts(m: dict, n_experts: int, seed: int,
                  bench: Path = BENCH_DIR
                  ) -> Tuple[List[Tuple[str, dict]], int]:
    """Host trees of ``n_experts`` experts for ``seed``: ``[(name, tree)]``
    and the bytes of one expert. With one expert, the base is the expert."""
    base = jax.device_get(make_base(m, seed, bench))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(base))
    if n_experts == 1:
        return [("expert000", base)], nbytes
    leaves = np.asarray(jax.device_get(make_expert_leaves(m, seed, n_experts,
                                                          bench)))
    leaf = spec.arch(m, bench).EXPERT_LEAF
    return [(f"expert{i:03d}", _with(base, leaf, leaves[i]))
            for i in range(n_experts)], nbytes
