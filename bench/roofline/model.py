"""FLOPs the model needs for the tokens a step processes, for step MFU.

Per token, 2 FLOPs per weight of the layers' projections and FFN. Attention
at the live length: a decode token with ``n`` live positions costs
4 * n * Hq * dh per layer; a prefill prompt of ``s`` tokens costs
4 * Hq * dh * s * (s + 1) / 2 per layer (causal). The output head is needed
once per decode token and once per prompt (its last position)."""


def _per_layer_weights(m):
    D, F = m["hidden_size"], m["intermediate_size"]
    Hq, Hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    return D * Hq * dh + 2 * D * Hkv * dh + Hq * dh * D + 3 * D * F


def decode_flops(m: dict, live: list) -> float:
    L, D, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    Hq, dh = m["num_attention_heads"], m["head_dim"]
    dense = 2 * (L * _per_layer_weights(m) + D * V)
    return len(live) * dense + L * 4 * Hq * dh * sum(live)


def prefill_flops(m: dict, lengths: list) -> float:
    L, D, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    Hq, dh = m["num_attention_heads"], m["head_dim"]
    out = 0.0
    for s in lengths:
        out += 2 * s * L * _per_layer_weights(m) + 2 * D * V
        out += L * 4 * Hq * dh * s * (s + 1) / 2
    return out
