"""Build one configuration's deployment of the program under test.

Everything here goes through the program's normal serving path: the
composition of experts over the host store and the HBM weight tier, and
``ServingEngine`` with the configuration's backend, prefill mode and
buckets. The program's ``ModelConfig`` comes from the configuration's
architecture module (``spec.arch``), weights from ``benchlib.weights``; the
benchmark keeps its own host trees for the reference and marks them
read-only.

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
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import BENCH_DIR, spec, weights


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

    def __init__(self, m: dict, seed: int, bench: Path = BENCH_DIR):
        from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
        from repro.serving import ServingEngine

        dep = m["deployment"]
        self.m, self.dep = m, dep
        self.cfg = spec.arch(m, bench).model_config(m)
        t = time.perf_counter()
        hosts, self.expert_bytes = weights.build_experts(m, dep["experts"],
                                                         seed, bench)
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
        self.sync()
        return time.perf_counter() - t

    def sync(self) -> None:
        """Wait until the device has written every array the engine's K/V
        pool holds, whatever the pool names them."""
        pool = self.engine.pool
        jax.block_until_ready([x for x in vars(pool).values()
                               if isinstance(x, jax.Array)])

    def fill_tier(self, popularity: List[str]) -> None:
        """Load the most popular experts into the HBM tier, the most
        popular last (most recently used), as a long-running server would
        hold them."""
        for name in reversed(popularity[:self.dep["hbm_tier_experts"]]):
            self.coe.cache.activate(name)

    def close(self) -> None:
        """Free the program's device state (weight tier, pool, compiled
        steps), so the reference can run in the room it held. The engine
        drops its pool object, so every array the pool holds goes, whatever
        its name; no local reference to the engine outlives the collection,
        which frees the engine's reference cycles."""
        self.coe.cache.close()
        for name in list(self.coe.cache.expert_ids()):
            self.coe.cache.drop(name)
        self.engine.pool = self.engine._params = None
        self.engine = self.coe = None
        import gc
        gc.collect()
