"""Paged GQA decode attention (``kernels/flash_attention``,
``_decode_paged_kernel``): what one call, one layer of one decode round,
needs. Per active lane with ``n`` live positions (the step's own token
included): QK^T and PV, 4 * n * Hq * dh FLOPs; its live K and V in bfloat16
read once, the query in and the output out, and its block-table entries.

The kernel is the Pallas call that takes two pool arrays
``[rows, block, Hkv, dh]`` and returns ``[B, Hq/Hkv, Hkv, dh]``."""
from benchlib.rooflines import shape


def match(op, m: dict) -> bool:
    Hq, Hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    block = m["deployment"]["block"]
    pools = [t for t in op.operands
             if shape(t)[1][1:] == (block, Hkv, dh)]
    return len(pools) == 2 and shape(op.result)[1][1:] == (Hq // Hkv, Hkv, dh)


def cost(m: dict, live: list, n_slots: int):
    Hq, Hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    block = m["deployment"]["block"]
    toks = sum(live)
    flops = 4 * toks * Hq * dh
    nbytes = (2 * 2 * toks * Hkv * dh                 # K and V, bf16
              + 2 * 2 * len(live) * Hq * dh          # q in, o out, bf16
              + 4 * sum(-(-n // block) for n in live))  # table entries
    return flops, nbytes
