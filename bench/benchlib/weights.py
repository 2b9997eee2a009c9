"""Weights made from ``--seed`` by the benchmark itself, in bfloat16.

The tree has the layout the serving program takes (layers stacked on a
leading axis). One jitted call makes a whole base model on the device; one
more makes every expert's own down-projections. Experts share every other
leaf of the base on the host, so building 8 experts copies the base to the
host once; each expert still moves its whole tree host->HBM when it is
switched in, because the host store hands the program whole trees.

Scales: embeddings N(0, 1); every projection N(0, 1/fan_in); norm scales 1.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the one leaf every expert draws afresh (so experts' logits differ
# entirely, and a request served with another expert's bytes shows)
EXPERT_LEAF = ("layers", "mlp", "wo")


def shapes(m: dict) -> Dict:
    """Leaf shapes and init std of the tree for model dict ``m``."""
    D, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    Hq, Hkv = m["num_attention_heads"], m["num_key_value_heads"]
    dh, F = m["head_dim"], m["intermediate_size"]
    return {
        "embed": {"tok": ((V, D), 1.0)},
        "final_norm": {"scale": ((D,), None)},
        "lm_head": ((D, V), 1 / math.sqrt(D)),
        "layers": {
            "attn": {"norm": {"scale": ((L, D), None)},
                     "wq": ((L, D, Hq, dh), 1 / math.sqrt(D)),
                     "wk": ((L, D, Hkv, dh), 1 / math.sqrt(D)),
                     "wv": ((L, D, Hkv, dh), 1 / math.sqrt(D)),
                     "wo": ((L, Hq, dh, D), 1 / math.sqrt(Hq * dh))},
            "mlp_norm": {"scale": ((L, D), None)},
            "mlp": {"wi_gate": ((L, D, F), 1 / math.sqrt(D)),
                    "wi_up": ((L, D, F), 1 / math.sqrt(D)),
                    "wo": ((L, F, D), 1 / math.sqrt(F))}},
    }


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _draw(key, shape, std):
    if std is None:
        return jnp.ones(shape, jnp.bfloat16)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def make_base(m: dict, seed: int):
    """Device tree of the base model for ``seed``, made in one jitted call."""
    spec = shapes(m)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_draw(k, s, std) for k, (s, std) in zip(keys, leaves)])

    return build(_key(seed, 0))


def make_expert_leaves(m: dict, seed: int, n: int):
    """Device array ``(n, ...)`` of each expert's own ``EXPERT_LEAF``, made
    in one jitted call; expert ``i`` draws from stream ``i + 1``."""
    s, std = _get(shapes(m), EXPERT_LEAF)
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


def build_experts(m: dict, n_experts: int, seed: int
                  ) -> Tuple[List[Tuple[str, dict]], int]:
    """Host trees of ``n_experts`` experts for ``seed``: ``[(name, tree)]``
    and the bytes of one expert. With one expert, the base is the expert."""
    base = jax.device_get(make_base(m, seed))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(base))
    if n_experts == 1:
        return [("expert000", base)], nbytes
    leaves = np.asarray(jax.device_get(make_expert_leaves(m, seed,
                                                          n_experts)))
    return [(f"expert{i:03d}", _with(base, EXPERT_LEAF, leaves[i]))
            for i in range(n_experts)], nbytes
