"""``run.py`` as a program: it refuses to run without a chip, the files it
names exist and follow the benchmark's rules, every configuration builds a
model the fused backend takes, and a cell, configuration, traffic mix and
per-layer metric added as new files are found by name."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from toy import BENCH, ROOT, TOY_MODEL, make

from benchlib import cell as cellmod, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(["--workload", "dsllm7b-coe-zipf", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode == 2, p.stderr
    assert "{" not in p.stdout
    assert "platform=cpu" in p.stdout


def test_exits_nonzero_in_a_bare_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "dsllm7b-coe-zipf", "--seed", "1",
              "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("name", [c["name"] for c in spec.benchmark()[
    "configs"]])
def test_configs_build_a_model_the_fused_backend_takes(name):
    from repro.serving.backends import FusedPagedBackend
    m = spec.config(name)
    arch = spec.arch(m)
    arch.check_config(m)
    if m["model_type"] == "llama":
        FusedPagedBackend(arch.model_config(m), scratch_row=0)
    for k in m["reduced"]:
        assert m["published"][k] != m[k]


def test_benchmark_file_follows_the_rules():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51
    metrics = b["end_to_end"] + b["per_layer"]
    names = [x["name"] for x in b["configs"] + b["workloads"] + metrics]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).exists()
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert c["reduced"] == spec.config(c["name"])["reduced"]
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert any(c["name"] == w["config"] for c in b["configs"])
    assert any(x["name"] == "setup_s" for x in b["end_to_end"])
    for x in b["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.25
        assert x["source"] in ("host_clock", "device_trace")
    for x in metrics:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert set(x.get("workloads", [])) <= cells
    e2e = {x["name"]: x for x in b["end_to_end"]}
    for x in b["per_layer"]:
        assert x["moves"] in e2e and 1 <= len(x["layer"]) <= 200
        for w in x["workloads"]:
            assert w in e2e[x["moves"]].get("workloads", [w])
    for w in cells:   # setup_s, one more end-to-end and one layer metric
        assert len(spec.metrics_of(w, "end_to_end")) >= 2
        assert spec.metrics_of(w, "per_layer")


def test_a_new_cell_config_mix_and_metric_are_found(tmp_path):
    root = make(tmp_path)
    (root / "bench/metrics/toy_rounds.py").write_text(
        "def read(rec, ctx):\n"
        "    return rec.counters['serve.decode_rounds']\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "toy_rounds", "unit": "rounds",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "tpot_p50_ms",
                           "workloads": ["toy-open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.workload("toy-open", root)
    res = cellmod.run_cell(cell, 2**33 + 1, 1.5, True, time.perf_counter(),
                           bench=root / "bench", root=root)
    assert res["correct"], res["checks"]
    assert res["metrics"]["toy_rounds"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert spec.config("toy-llama", root / "bench") == TOY_MODEL


def test_a_bursty_shared_prefix_cell_is_added_as_files_alone(tmp_path):
    """A mix with bursty arrivals, two length classes and shared prefixes,
    on a deployment that turns the engine's prefix sharing on: new data
    files only, run whole and checked correct."""
    model = dict(TOY_MODEL, name="toy-shared", deployment=dict(
        TOY_MODEL["deployment"], engine={"prefix_sharing": True},
        store={"class": "HostMemoryStore"}))
    mix = {"loop": "open", "rate_per_s": 8.0,
           "arrivals": {"process": "gamma", "cv": 3.0},
           "classes": [
               {"share": 0.25, "prompt": {"median": 24, "sigma": 0.1,
                                          "min": 20, "max": 28},
                "output": {"median": 12, "sigma": 0.2, "min": 8, "max": 16}},
               {"share": 0.75, "prompt": {"median": 8, "sigma": 0.2,
                                          "min": 4, "max": 12},
                "output": {"median": 10, "sigma": 0.2, "min": 8, "max": 14}}],
           "prefix": {"share": 0.6, "count": 2,
                      "length": {"median": 16, "sigma": 0.1, "min": 16,
                                 "max": 16}},
           "experts": {"zipf_s": 1.1}, "drain_cap_s": 60,
           "sample_tokens": 150}
    root = make(tmp_path, model=model, traffic=mix, config="toy-shared")
    cell = spec.workload("toy-open", root)
    res = cellmod.run_cell(cell, 2**31 + 3, 1.5, False, time.perf_counter(),
                           bench=root / "bench", root=root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
