"""Three-tier memory system (paper §III-B, §IV, §V-A).

SN40L tiers → TPU-node analogues:
    SRAM (520 MB PMUs)      → VMEM        (managed by Pallas BlockSpecs)
    HBM  (64 GB, 1.8 TB/s)  → device HBM  (software-managed expert cache)
    DDR  (1.5 TB, 200 GB/s) → host DRAM   (expert capacity tier)

This module provides:
  * tier presets (SN40L node, TPU v5e host, DGX A100/H100) used by the
    bandwidth model and the Table V / Fig 12 benchmarks;
  * ``StaticAllocator`` — the paper's static lifetime-based garbage
    collection: symbols with disjoint lifetimes share device addresses;
  * ``spill_order`` — the paper's bandwidth-aware spill heuristic: when HBM
    does not fit, spill symbols with the smallest aggregate transfer
    footprint first (weights stay, low-reuse intermediates go);
  * ``HBMBudget`` / ``plan_hbm_budget`` — the serving-time split of the HBM
    tier between resident expert weights (the LRU cache of
    ``core.switching``) and the paged KV pool of ``serving.kvcache``:
    resident-experts vs concurrent-requests as ONE explicit tradeoff.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple


GiB = 1024 ** 3
GBps = 1e9


@dataclass(frozen=True)
class MemoryTier:
    name: str
    capacity: int          # bytes
    bandwidth: float       # bytes/s


@dataclass(frozen=True)
class MachineTiers:
    """Per-socket tiers + the capacity-tier -> HBM copy bandwidth per node."""
    name: str
    sram: MemoryTier
    hbm: MemoryTier
    capacity: MemoryTier           # DDR (SN40L) or host DRAM (TPU/DGX)
    copy_bw_node: float            # capacity->HBM bytes/s, whole node
    sockets_per_node: int
    peak_flops_bf16: float         # per socket
    # achievable fraction of HBM bandwidth on fused decode. Paper §VI-B:
    # SN40L sustains ~85% with whole-decoder fusion; optimized GPU decoders
    # "rarely exceed 50%". Our Pallas fused-decode path targets the SN40L
    # regime on TPU.
    hbm_efficiency: float = 0.85


# --- presets (paper Table II, DGX specs from paper §VI-C refs) -----------
SN40L_NODE = MachineTiers(
    name="sn40l",
    sram=MemoryTier("sram", int(0.52 * GiB), 400e12),
    hbm=MemoryTier("hbm", 64 * GiB, 1.8e12),
    capacity=MemoryTier("ddr", int(1.5 * 1024) * GiB, 200 * GBps),
    copy_bw_node=1e12,             # >1 TB/s aggregate DDR->HBM (paper §VI-C)
    sockets_per_node=8,
    peak_flops_bf16=638e12,
    hbm_efficiency=0.85,           # paper §VI-B
)

DGX_A100 = MachineTiers(
    name="dgx-a100",
    sram=MemoryTier("sram", int(0.04 * GiB), 200e12),
    hbm=MemoryTier("hbm", 80 * GiB, 2.0e12),
    capacity=MemoryTier("host", 2048 * GiB, 200 * GBps),
    copy_bw_node=32 * GBps,        # host->GPU PCIe (paper: 32 GB/s)
    sockets_per_node=8,
    peak_flops_bf16=312e12,
    hbm_efficiency=0.45,           # paper §VI-B: "rarely exceed 50%"
)

DGX_H100 = MachineTiers(
    name="dgx-h100",
    sram=MemoryTier("sram", int(0.05 * GiB), 400e12),
    hbm=MemoryTier("hbm", 80 * GiB, 3.35e12),
    capacity=MemoryTier("host", 2048 * GiB, 200 * GBps),
    copy_bw_node=64 * GBps,        # paper: 64 GB/s
    sockets_per_node=8,
    peak_flops_bf16=989e12,
    hbm_efficiency=0.5,
)

TPU_V5E_NODE = MachineTiers(
    name="tpu-v5e",
    sram=MemoryTier("vmem", 128 * 1024 ** 2, 400e12),
    hbm=MemoryTier("hbm", 16 * GiB, 819 * GBps),
    capacity=MemoryTier("host", 512 * GiB, 200 * GBps),
    copy_bw_node=8 * 32 * GBps,    # 8 chips/host x PCIe-class DMA
    sockets_per_node=8,
    peak_flops_bf16=197e12,
    hbm_efficiency=0.8,            # our fused decode path (kernels/)
)

MACHINES = {m.name: m for m in (SN40L_NODE, DGX_A100, DGX_H100, TPU_V5E_NODE)}

# ``device_kind`` as JAX reports it -> the node preset that models that chip
MACHINES_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E_NODE}


def machine_for(devices) -> MachineTiers:
    """The node preset for the devices a node runs on. On an accelerator it
    comes from ``MACHINES_BY_DEVICE_KIND``, and a kind missing there is an
    error, not a default. Emulated CPU sockets model a TPU v5e node."""
    d = devices[0]
    if d.platform == "cpu":
        return TPU_V5E_NODE
    try:
        return MACHINES_BY_DEVICE_KIND[d.device_kind]
    except KeyError:
        raise KeyError(
            f"no MachineTiers preset for device kind {d.device_kind!r}; "
            f"add one to MACHINES_BY_DEVICE_KIND") from None


# ----------------------------------------------------------------------
# Serving-time HBM split: expert weights vs paged KV pool
# ----------------------------------------------------------------------

@dataclass(frozen=True)
class HBMBudget:
    """How one HBM tier is divided at serving time.

    ``weights_bytes`` caps the ``HBMWeightCache`` (how many experts stay
    resident, i.e. how many switches are HBM hits); ``kv_bytes`` caps the
    ``PagedKVCache`` (how many requests decode concurrently). The two sum to
    ``total_bytes`` — growing one shrinks the other, which is exactly the
    CoE serving tradeoff of paper §V-B/§VI-C.
    """
    total_bytes: int
    weights_bytes: int
    kv_bytes: int

    def resident_experts(self, expert_bytes: int) -> int:
        return self.weights_bytes // max(expert_bytes, 1)

    def kv_blocks(self, block_bytes: int) -> int:
        return self.kv_bytes // max(block_bytes, 1)


def plan_hbm_budget(total_bytes: int, expert_bytes: int, block_bytes: int,
                    *, min_resident_experts: int = 2,
                    kv_fraction: float = 0.2) -> HBMBudget:
    """Split an HBM tier between the expert LRU cache and the KV pool.

    Reserves ``kv_fraction`` of the tier for KV, but never shrinks the
    weight share below ``min_resident_experts`` experts (the active expert
    plus at least one prefetch target — otherwise every switch is a miss and
    prefetch can never overlap decode) and never below one KV block.
    """
    if total_bytes < min_resident_experts * expert_bytes + block_bytes:
        raise MemoryError(
            f"HBM tier of {total_bytes} bytes cannot hold "
            f"{min_resident_experts} experts ({expert_bytes} B each) plus "
            f"one KV block ({block_bytes} B)")
    kv = int(total_bytes * kv_fraction)
    floor_w = min_resident_experts * expert_bytes
    kv = min(kv, total_bytes - floor_w)
    kv = max(kv, block_bytes)
    return HBMBudget(total_bytes=total_bytes,
                     weights_bytes=total_bytes - kv, kv_bytes=kv)


# ----------------------------------------------------------------------
# Static lifetime allocator (paper §V-A)
# ----------------------------------------------------------------------

@dataclass(frozen=True)
class Symbol:
    name: str
    size: int              # bytes
    first_use: int         # step index
    last_use: int
    read_only: bool = False
    transfer_footprint: int = 0   # aggregate bytes moved if spilled (reuse x size)


@dataclass
class Allocation:
    offsets: Dict[str, int]
    peak: int


def allocate_static(symbols: Sequence[Symbol], align: int = 512) -> Allocation:
    """Greedy lifetime-based allocation: symbols with disjoint [first,last]
    lifetimes may share addresses. This is the paper's 'static garbage
    collection' — no runtime allocator, no CPU round-trips.
    """
    def rnd(x):
        return (x + align - 1) // align * align

    events = sorted(symbols, key=lambda s: (s.first_use, -s.size))
    # free list of (offset, size) holes; live: name -> (offset, size, last_use)
    live: Dict[str, Tuple[int, int, int]] = {}
    holes: List[Tuple[int, int]] = []
    peak = 0
    offsets: Dict[str, int] = {}
    top = 0

    for sym in events:
        # retire symbols whose lifetime ended before this first_use
        for n, (off, sz, last) in list(live.items()):
            if last < sym.first_use:
                holes.append((off, sz))
                del live[n]
        holes.sort()
        # coalesce adjacent holes
        merged: List[Tuple[int, int]] = []
        for off, sz in holes:
            if merged and merged[-1][0] + merged[-1][1] == off:
                merged[-1] = (merged[-1][0], merged[-1][1] + sz)
            else:
                merged.append((off, sz))
        holes = merged
        need = rnd(sym.size)
        # best-fit
        best = None
        for i, (off, sz) in enumerate(holes):
            if sz >= need and (best is None or sz < holes[best][1]):
                best = i
        if best is not None:
            off, sz = holes.pop(best)
            offsets[sym.name] = off
            if sz > need:
                holes.append((off + need, sz - need))
        else:
            offsets[sym.name] = top
            top += need
        live[sym.name] = (offsets[sym.name], need, sym.last_use)
        peak = max(peak, top)
    return Allocation(offsets, peak)


def spill_order(symbols: Sequence[Symbol]) -> List[Symbol]:
    """Paper §V-A: spill candidates ordered by aggregate transfer footprint
    ascending — symbols that would cost the least DDR bandwidth go first.
    Weights (high reuse during decode) naturally sort last and stay in HBM."""
    return sorted(symbols, key=lambda s: (s.transfer_footprint, s.size))


def plan_placement(symbols: Sequence[Symbol], hbm_capacity: int,
                   align: int = 512) -> Tuple[Allocation, List[str]]:
    """Allocate into HBM; spill by ``spill_order`` until the peak fits.
    Returns (allocation of resident symbols, spilled symbol names)."""
    resident = list(symbols)
    spilled: List[str] = []
    order = spill_order(symbols)
    k = 0
    while True:
        alloc = allocate_static(resident, align)
        if alloc.peak <= hbm_capacity or not resident:
            return alloc, spilled
        victim = order[k].name
        k += 1
        resident = [s for s in resident if s.name != victim]
        spilled.append(victim)
