"""Out-projection, residual, RMSNorm and SwiGLU FFN of one layer for the
decode batch (``kernels/fused_decode``, ``_oproj_ffn_kernel``): what one
call needs. Per active lane, 2 FLOPs per weight of Wo (Hq*dh x D) and of
the FFN (3 x D x F); the bfloat16 weights read once, the norm scale, and
each lane's residual in, attention output in and residual out.

The kernel is the Pallas call that takes the FFN weights ``[D, F]`` twice
and ``[F, D]``. No per-layer metric reads this share: XLA stages part of
the kernel's weights into on-chip memory with copies outside the kernel,
so the kernel's own time can beat the HBM roofline (``PERF.md``). It
names the kernel in the trace's breakdown."""
from benchlib.rooflines import shape


def match(op, m: dict) -> bool:
    D, F = m["hidden_size"], m["intermediate_size"]
    shapes = [shape(t)[1] for t in op.operands]
    return shapes.count((D, F)) == 2 and (F, D) in shapes


def cost(m: dict, live: list, n_slots: int):
    D, F = m["hidden_size"], m["intermediate_size"]
    HD = m["num_attention_heads"] * m["head_dim"]
    weights = HD * D + 3 * D * F
    b = len(live)
    flops = 2 * b * weights
    nbytes = 2 * weights + 2 * D + 2 * b * (D + HD + D)
    return flops, nbytes
