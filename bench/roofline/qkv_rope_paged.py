"""RMSNorm, Q/K/V projections and per-lane RoPE of one layer for the
decode batch (``kernels/fused_decode``, ``_qkv_paged_kernel``): what one
call needs. Per active lane, 2 FLOPs per weight of Wq, Wk and Wv
(D x (Hq + 2 Hkv) dh); the bfloat16 weights read once, the norm scale, each
lane's residual in and its q, k and v out.

The kernel is the Pallas call that returns ``[Hq + 2 Hkv, B, dh]``. No
per-layer metric reads this share, for the reason given in
``oproj_ffn_swiglu.py``; it names the kernel in the trace's breakdown."""
from benchlib.rooflines import shape


def match(op, m: dict) -> bool:
    Hq, Hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    dims = shape(op.result)[1]
    return len(dims) == 3 and dims[0] == Hq + 2 * Hkv and dims[2] == dh


def cost(m: dict, live: list, n_slots: int):
    D = m["hidden_size"]
    Hq, Hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    weights = D * (Hq + 2 * Hkv) * dh
    b = len(live)
    flops = 2 * b * weights
    nbytes = 2 * weights + 2 * D + 2 * b * (D + (Hq + 2 * Hkv) * dh)
    return flops, nbytes
