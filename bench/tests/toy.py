"""A toy cell for CPU tests: a copy of ``bench/`` with a tiny model, a
tiny traffic mix and a ``BENCHMARK.json`` naming them, added as new files
the way a later change would add a cell."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TOY_MODEL = {
    "name": "toy-llama", "source": "toy", "model_type": "llama",
    "hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 512,
    "vocab_size": 8192, "hidden_act": "silu", "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "attention_bias": False, "mlp_bias": False,
    "tie_word_embeddings": False, "reduced": [], "reference": "llama",
    "deployment": {"experts": 3, "hbm_tier_experts": 2, "n_slots": 4,
                   "block": 8, "max_len": 64, "backend": "fused",
                   "prefill_mode": "packed",
                   "prefill_buckets": [16, 32, 64]},
    "check": {"gap_limit": 0.06},
}

# granite-8b-code widths at 4 layers: the model of the recorded chip trace
# in data/ and of the hand counts of the roofline functions
GRANITE = {
    "name": "granite-8b", "hidden_size": 4096, "num_hidden_layers": 4,
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
    "intermediate_size": 14336, "vocab_size": 49152,
    "deployment": {"n_slots": 32, "block": 16, "max_len": 2048},
}

TOY_TRAFFIC = {
    "loop": "open", "rate_per_s": 8.0, "n_requests": 64,
    "prompt": {"median": 16, "sigma": 0.4, "min": 8, "max": 40},
    "output": {"median": 12, "sigma": 0.3, "min": 8, "max": 20},
    "experts": {"zipf_s": 1.1}, "drain_cap_s": 60,
    "sample_tokens": 150,
}


def make(tmp: Path, model=None, traffic=None, cell="toy-open",
         config="toy-llama") -> Path:
    """A checkout under ``tmp`` with the toy cell added; returns its root."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / f"bench/configs/{config}.json").write_text(
        json.dumps(model or TOY_MODEL))
    (root / "bench/traffic/toy-mix.json").write_text(
        json.dumps(traffic or TOY_TRAFFIC))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": cell, "config": config,
                           "traffic": "toy-mix", "chips": 1, "why": "toy"})
    b["end_to_end"] = [dict(x, workloads=x["workloads"] + [cell])
                       if "workloads" in x else x for x in b["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root
