"""The output check at toy size on the CPU: the program passes, the fp8
control fails, and each fault a one-chip serving cell can have, planted
under a whole run, turns ``correct`` false. (A one-chip cell has no
exchange between chips to leave out.)"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy import make

from benchlib import cell as cellmod, spec


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make(tmp_path_factory.mktemp("toy"))


def run(root, fault=None, control=False, seed=3):
    cell = spec.workload("toy-open", root)
    return cellmod.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                            bench=root / "bench", root=root,
                            control=control, fault=fault)


def test_program_passes_and_control_fails(toy_root):
    res = run(toy_root, control=True)
    c = res["checks"]
    assert res["correct"], c
    assert c["gap"]["value"] <= c["gap"]["limit"] < c["control_gap"]["value"]


def state_unchanged(dep):
    """The decode step hands back the pool it was given: no K/V lands."""
    r = dep.engine.runner
    fn = r.extend

    def extend(params, pk, pv, *a):
        keep = jnp.copy(pk), jnp.copy(pv)
        logits, _, _ = fn(params, pk, pv, *a)
        return (logits, *keep)
    r.extend = extend


def half_batch_left_out(dep):
    """Every other active lane is left out of the decode step."""
    r = dep.engine.runner
    fn = r.extend

    def extend(params, pk, pv, tables, lengths, active, toks):
        act = np.array(active)
        act[np.nonzero(act)[0][::2]] = False
        return fn(params, pk, pv, tables, lengths, jnp.asarray(act), toks)
    r.extend = extend


def token_altered(dep):
    """Each request's second token is altered where it is produced."""
    eng = dep.engine
    fn = eng.policy.round
    V = eng.cfg.vocab_size

    def round_(params, active):
        out = fn(params, active)
        for i, toks in out.items():
            if len(eng.slots[i].generated) == 1:
                toks[0] = (toks[0] + 1) % V
        return out
    eng.policy.round = round_


def wrong_expert(dep):
    """The host store hands back the next expert's weights, and the tier
    is emptied so every expert is loaded through it."""
    store, cache = dep.coe.store, dep.coe.cache
    names = dep.expert_names()
    get = store.get
    store.get = lambda n: get(names[(names.index(n) + 1) % len(names)])
    for n in list(cache.expert_ids()):
        cache.drop(n)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered, wrong_expert])
def test_a_fault_under_the_timed_path_is_caught(toy_root, fault):
    res = run(toy_root, fault=fault)
    assert not res["correct"]
    assert res["checks"]["gap"]["value"] > res["checks"]["gap"]["limit"]


def test_reference_agrees_with_the_program_in_float32(toy_root):
    """The benchmark's reference and the program's own float32 forward
    give the same logits on the same weights (a toy size, on the CPU)."""
    from benchlib import weights
    from repro.models.reference import reference_logits
    bench = toy_root / "bench"
    m = spec.config("toy-llama", bench)
    params = jax.device_get(weights.make_base(m, 5, bench))
    ref = spec.reference(m, bench)
    toks = np.random.RandomState(0).randint(0, m["vocab_size"], 40)
    got = reference_logits(spec.arch(m, bench).model_config(m), params, toks,
                           pad_to=64)
    served = np.argmax(got[19:38], -1).astype(np.int32)
    gaps = ref.gaps(m, params, toks[:39], 19, served, 64)
    assert np.max(gaps) < 1e-4
    wrong = (served + 1) % m["vocab_size"]
    assert np.min(ref.gaps(m, params, toks[:39], 19, wrong, 64)) > 1e-3


def test_close_frees_every_array_the_pool_holds(toy_root):
    """The reference runs in the room the program held: closing the
    deployment frees each device array of the K/V pool, whatever the pool
    names it, though the engine sits in reference cycles."""
    from benchlib import traffic
    from benchlib.deploy import Deployment
    bench = toy_root / "bench"
    m = spec.config("toy-llama", bench)
    tp = spec.traffic("toy-mix", bench)
    dep = Deployment(m, 3, bench)
    t = traffic.generate(tp, 3, dep.expert_names(), m["vocab_size"], 1.0)
    dep.warm(t.popularity, cellmod.max_pack(m, tp))
    pool = dep.engine.pool
    pool.latent = jnp.full((3, 5, 7), 1.5, jnp.float32)
    kinds = [(x.shape, x.dtype) for x in vars(pool).values()
             if isinstance(x, jax.Array)]
    del pool
    held = lambda: sum((a.shape, a.dtype) in kinds for a in jax.live_arrays())
    before = held()
    dep.close()
    assert before - held() == len(kinds) >= 3
