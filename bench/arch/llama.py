"""Llama architecture: RMSNorm, grouped-query attention with full RoPE,
SwiGLU FFN, no biases, an untied output head.

Everything the harness knows of an architecture is in one module like this
one, found by the configuration's ``model_type``: the program's
``ModelConfig``, the weight tree the benchmark draws, the model's FLOPs for
``step_mfu``, and the Pallas kernels whose rooflines the trace is read
against.

Weights (``shapes``): embeddings N(0, 1); every projection N(0, 1/fan_in);
norm scales 1; layers stacked on a leading axis, as the serving program
takes them.

FLOPs: per token, 2 FLOPs per weight of the layers' projections and FFN.
Attention at the live length: a decode token with ``n`` live positions
costs 4 * n * Hq * dh per layer; a prefill prompt of ``s`` tokens costs
4 * Hq * dh * s * (s + 1) / 2 per layer (causal). The output head is needed
once per decode token and once per prompt (its last position).
"""
from __future__ import annotations

import math
from typing import Dict

# the one leaf every CoE expert draws afresh (so experts' logits differ
# entirely, and a request served with another expert's bytes shows)
EXPERT_LEAF = ("layers", "mlp", "wo")

# the fused decode step's Pallas kernels, one call of each per layer
KERNELS = ("qkv_rope_paged", "decode_paged", "oproj_ffn_swiglu")


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


def check_config(m: dict) -> None:
    """Raise ``ValueError`` where the configuration's widths disagree with
    each other or with the ``ModelConfig`` built from them."""
    cfg = model_config(m)
    if (cfg.d_model, cfg.n_kv_heads) != (m["hidden_size"],
                                         m["num_key_value_heads"]):
        raise ValueError(f"{m['name']}: ModelConfig widths differ from the "
                         "configuration's")
    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError(f"{m['name']}: head_dim x heads != hidden_size")


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


def _per_layer_weights(m):
    D, F = m["hidden_size"], m["intermediate_size"]
    Hq, Hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    return D * Hq * dh + 2 * D * Hkv * dh + Hq * dh * D + 3 * D * F


def decode_flops(m: dict, live: list) -> float:
    """Model FLOPs of one decode round over lanes of ``live`` lengths."""
    L, D, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    Hq, dh = m["num_attention_heads"], m["head_dim"]
    dense = 2 * (L * _per_layer_weights(m) + D * V)
    return len(live) * dense + L * 4 * Hq * dh * sum(live)


def prefill_flops(m: dict, lengths: list) -> float:
    """Model FLOPs of prefilling prompts of ``lengths`` tokens."""
    L, D, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    Hq, dh = m["num_attention_heads"], m["head_dim"]
    out = 0.0
    for s in lengths:
        out += 2 * s * L * _per_layer_weights(m) + 2 * D * V
        out += L * 4 * Hq * dh * s * (s + 1) / 2
    return out


def calls_per_round(m: dict, kernel: str) -> int:
    """Calls of ``kernel`` in one decode round: one per layer."""
    return m["num_hidden_layers"]
