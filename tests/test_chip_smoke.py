"""``chip_smoke.py``'s phases on the CPU at ``reduced(granite-8b)``.

The smoke script itself refuses to run without a TPU; these tests call its
phase functions at reduced widths with the Pallas kernels in interpret
mode, so a broken phase is caught before any chip time is spent. Also the
compile-cache placement rule shared by the entry points.
"""
import importlib.util
import pathlib

import pytest

from repro.configs import get_config, reduced
from repro.launch.compile_cache import REPO_CACHE, compile_cache_dir
from repro.models.reference import BF16_TOL

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_config_cuts_depth_only(smoke):
    cfg, cuts = smoke.smoke_config()
    full = get_config("granite-8b")
    assert cuts == ["n_layers 36 -> 4"] and cfg.n_layers == 4
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (full.d_model, full.n_heads,
                                          full.n_kv_heads, full.head_dim,
                                          full.d_ff, full.vocab_size)
    assert smoke.smoke_config(reduced(full))[1] == []


def test_smoke_phases_on_reduced_granite(smoke):
    """Build experts, warm the fused engine, plan memory, serve traffic
    that must switch and evict, and compare logits with the float32
    reference: the one-chip path end to end at reduced widths."""
    from repro.launch.serve import build_coe
    from repro.serving import ServingEngine

    cfg, _ = smoke.smoke_config(reduced(get_config("granite-8b")))
    coe, nbytes = build_coe(cfg, 4, 2, seed=0)
    engine = ServingEngine(coe, cfg, max_len=64, n_slots=4, block_size=8,
                           backend="fused")
    engine.warmup()
    plan = smoke.memory_plan(engine)
    assert plan["weights"] == 2 * nbytes
    assert plan["total"] == (plan["weights"] + plan["kv_pool"]
                             + plan["step_temps"])
    assert not plan["decode_has_kernel"]      # interpreted: no Mosaic call

    reqs = smoke.make_requests(cfg, coe.expert_names(), n=8,
                               prompt_lens=(8, 24), new_tokens=4)
    res = smoke.serve(engine, reqs)
    assert (res["requests"], res["tokens"]) == (8, 32)
    assert engine.stats.switches >= 1 and coe.cache.stats.evictions >= 1
    assert coe.cache.stats.prefetch_failures == 0

    lines = []
    worst = smoke.check_logits(engine, cfg, smoke.first_prompts(reqs),
                               emit=lines.append)
    assert len(lines) == 4 and 0 < worst <= BF16_TOL
    assert engine.pool.stats.blocks_in_use == 0
    coe.cache.close()


def test_smoke_refuses_without_tpu(smoke, monkeypatch, capsys, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir_rule():
    assert compile_cache_dir({}) == str(REPO_CACHE)
    assert REPO_CACHE == ROOT / ".jax_cache"
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x/c"}) == "/x/c"
