"""Build one configuration's deployment of the program under test.

Everything here goes through the program's normal serving path: the
composition of experts over the host store and the HBM weight tier, and
``ServingEngine`` with the configuration's backend, prefill mode and
buckets. Weights come from ``benchlib.weights``; the benchmark keeps its own
host trees for the reference and marks them read-only.

Keys of a configuration's ``deployment``: ``experts`` (registered in the
capacity tier), ``hbm_tier_experts`` (the weight tier's size in experts),
``n_slots``, ``block``, ``max_len``, ``backend``, ``prefill_mode``,
``prefill_buckets``; optionally ``engine`` (further keyword arguments of
``ServingEngine``, such as ``{"prefix_sharing": true}``) and ``store``
(``{"class": <a class of repro.store>, "args": {...}}``, host memory when
absent).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights


def model_config(m: dict):
    """The program's ``ModelConfig`` for a configuration file's model keys
    (Llama architecture: RMSNorm, SwiGLU, full RoPE, no biases, untied)."""
    from repro.configs.base import ModelConfig
    if m.get("hidden_act", "silu") != "silu" or m.get("attention_bias") \
            or m.get("mlp_bias") or m.get("tie_word_embeddings"):
        raise ValueError(f"{m['name']}: not a plain Llama-architecture model")
    return ModelConfig(
        name=m["name"], family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"], norm="rms",
        act="swiglu", rope_style="full", rope_theta=float(m["rope_theta"]))


def make_store(spec):
    """The capacity-tier store ``deployment.store`` names, or None (the
    program's default, host memory)."""
    if not spec:
        return None
    import repro.store
    return getattr(repro.store, spec["class"])(**spec.get("args", {}))


def _freeze(tree):
    for x in jax.tree.leaves(tree):
        x.setflags(write=False)


class Deployment:
    """Experts in the host store, the engine built and warmed for the
    cell's own shapes, and the HBM tier filled with the most popular
    experts."""

    def __init__(self, m: dict, seed: int):
        from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
        from repro.serving import ServingEngine

        dep = m["deployment"]
        self.m, self.dep = m, dep
        self.cfg = model_config(m)
        t = time.perf_counter()
        hosts, self.expert_bytes = weights.build_experts(m, dep["experts"],
                                                         seed)
        self.build_s = time.perf_counter() - t
        self.hosts: Dict[str, dict] = {}
        coe = CompositionOfExperts(
            HashRouter(len(hosts)), None,
            hbm_capacity_bytes=dep["hbm_tier_experts"] * self.expert_bytes,
            store=make_store(dep.get("store")))
        for name, tree in hosts:
            _freeze(tree)
            self.hosts[name] = tree
            coe.register(ExpertHandle(name, self.cfg, tree))
        self.coe = coe
        self.engine = ServingEngine(
            coe, self.cfg, max_len=dep["max_len"], n_slots=dep["n_slots"],
            block_size=dep["block"], backend=dep["backend"],
            prefill_mode=dep["prefill_mode"],
            prefill_buckets=tuple(dep["prefill_buckets"]),
            **dep.get("engine", {}))

    def expert_names(self) -> List[str]:
        return self.coe.expert_names()

    def warm(self, popularity: List[str], max_pack: int) -> float:
        """Compile and run every shape the window uses, then fill the HBM
        tier. Returns the seconds taken."""
        t = time.perf_counter()
        eng = self.engine
        eng.warmup(expert=popularity[0])
        params = self.coe.cache.activate(popularity[0])
        pr = eng.prefill_runner
        if pr is not None:
            # admission takes argmax over the first n rows of a packed
            # call's logits: one small program per n the traffic can pack
            res = pr(params, [np.zeros((pr.buckets[0],), np.int32)])
            for n in range(1, max_pack + 1):
                jnp.argmax(res.logits[:n], axis=-1).block_until_ready()
        self.fill_tier(popularity)
        jax.block_until_ready((eng.pool.k, eng.pool.v))
        return time.perf_counter() - t

    def fill_tier(self, popularity: List[str]) -> None:
        """Load the most popular experts into the HBM tier, the most
        popular last (most recently used), as a long-running server would
        hold them."""
        for name in reversed(popularity[:self.dep["hbm_tier_experts"]]):
            self.coe.cache.activate(name)

    def close(self) -> None:
        """Free the program's device state (weight tier, pool, compiled
        steps), so the reference can run in the room it held."""
        self.coe.cache.close()
        for name in list(self.coe.cache.expert_ids()):
            self.coe.cache.drop(name)
        eng = self.engine
        eng.pool.k = eng.pool.v = None
        eng._params = None
        self.engine = self.coe = None
        import gc
        gc.collect()
