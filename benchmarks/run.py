"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the paper-comparable
ratio for that table). Measured numbers come from this CPU container where a
real measurement is meaningful (kernel-launch overheads, switching engine,
fusion wall-time); cross-machine latency/footprint projections come from the
calibrated bandwidth model (core/bandwidth_model.py) with the paper's own
hardware constants — the analytic path the paper itself uses for its DGX
comparisons (§VI-C).

    PYTHONPATH=src python -m benchmarks.run            # all tables
    PYTHONPATH=src python -m benchmarks.run --only fig11
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry

RESULTS = Path(__file__).resolve().parent.parent / "results"

ROWS = []


def _gated_metrics(values: dict) -> dict:
    """Publish the gated bench metrics as registry gauges and read the
    emitted dict back off a registry snapshot — the JSON the CI gate
    (``tools/check_bench.py``) consumes is a registry view, the same
    pipeline ``--metrics-port`` serves, not a hand-built dict."""
    reg = MetricsRegistry()
    for k, v in values.items():
        reg.gauge(k).set(float(v))
    snap = reg.snapshot()
    return {k: snap[k] for k in values}


def _results_dir() -> Path:
    """``results/`` is gitignored and may not exist on a fresh clone —
    every writer creates it on demand."""
    RESULTS.mkdir(parents=True, exist_ok=True)
    return RESULTS


def _ensure_host_devices(n: int):
    """The node sweep emulates ``n`` sockets; this must run before anything
    initializes the JAX backend (importing repro.node does not)."""
    from repro.node.topology import ensure_node_devices
    ensure_node_devices(n)


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.2f},{derived}"
    ROWS.append(row)
    print(row)


def _timeit(fn, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n * 1e6      # us


# ----------------------------------------------------------------------
# Table I: operational intensity vs fusion level (Monarch FFT pipeline)
# ----------------------------------------------------------------------
def bench_table1_intensity():
    """Paper Table I (39.5 / 102.6 / 410.4 flops/byte for their 1M-point
    Monarch). Our ledger uses N1=N2=256 factor matrices; the absolute
    numbers depend on factor size / dtype (unstated in the paper) — the
    reproduced CLAIM is the ordering and that fusion crosses the ~150
    flops/byte memory/compute boundary (A100 ridge point)."""
    from repro.kernels.monarch_fft import operational_intensity, monarch, ref
    for level in ("none", "gemm0_mul_t", "full"):
        oi = operational_intensity(16, 256, 256, fusion=level)
        emit(f"table1_intensity_{level}", 0.0,
             f"OI={oi:.1f}flops/byte,{'compute' if oi > 150 else 'memory'}"
             f"-bound_on_A100")
    # measured: fused (one jit) vs op-by-op with host dispatch between
    B, N1, N2 = 16, 256, 256
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 4)
    x = jax.random.normal(ks[0], (B, N1, N2))
    w0 = jax.random.normal(ks[1], (N1, N1)) / np.sqrt(N1)
    tw = jax.random.normal(ks[2], (N1, N2))
    w1 = jax.random.normal(ks[3], (N2, N2)) / np.sqrt(N2)
    fused = jax.jit(lambda x: ref.monarch_ref(x, w0, tw, w1))
    j_g0 = jax.jit(lambda x: jnp.einsum("ij,bjk->bik", w0, x))
    j_mul = jax.jit(lambda a: a * tw)
    j_t = jax.jit(lambda a: a.transpose(0, 2, 1))
    j_g1 = jax.jit(lambda at: jnp.einsum("ij,bjk->bik", w1, at))
    def unfused():
        a = j_g0(x); jax.block_until_ready(a)
        a = j_mul(a); jax.block_until_ready(a)
        a = j_t(a); jax.block_until_ready(a)
        return j_g1(a)
    tf = _timeit(lambda: fused(x))
    tu = _timeit(unfused)
    emit("table1_measured_fused", tf, f"speedup={tu/tf:.2f}x_vs_unfused")


# ----------------------------------------------------------------------
# Fig 10: fused vs unfused speedup per benchmark (decode/prefill/train)
# ----------------------------------------------------------------------
def bench_fig10_fusion_speedup():
    """Wall-clock: whole fused decoder-layer decode step as ONE jit vs one
    jit per op with host dispatch between (the paper's unfused baseline)."""
    from repro.kernels.fused_decode import ref as fd
    B, D, n_q, n_kv, dh, F, S = 8, 512, 8, 2, 64, 2048, 1024
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 8)
    x = jax.random.normal(ks[0], (B, D), jnp.float32)
    p = {
        "attn_norm": jnp.ones(D), "mlp_norm": jnp.ones(D),
        "w_qkv": jax.random.normal(ks[1], (D, (n_q + 2 * n_kv) * dh)) / 23,
        "w_o": jax.random.normal(ks[2], (n_q * dh, D)) / 23,
        "w_gate": jax.random.normal(ks[3], (D, F)) / 23,
        "w_up": jax.random.normal(ks[4], (D, F)) / 23,
        "w_down": jax.random.normal(ks[5], (F, D)) / 45,
    }
    kc = jax.random.normal(ks[6], (B, S, n_kv, dh))
    vc = jax.random.normal(ks[7], (B, S, n_kv, dh))
    pos = jnp.int32(S - 1)

    fused = jax.jit(lambda x, kc, vc: fd.decoder_layer_step_ref(
        x, p, kc, vc, pos, n_q=n_q, n_kv=n_kv, dh=dh))

    j_qkv = jax.jit(lambda x: fd.qkv_rope_ref(x, p["attn_norm"], p["w_qkv"],
                                              pos, n_q=n_q, n_kv=n_kv, dh=dh))
    j_dus = jax.jit(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
        c, u, i, 1))
    from repro.kernels.flash_attention.ref import decode_attention_ref
    j_attn = jax.jit(decode_attention_ref)
    j_oproj = jax.jit(lambda x, o: x + (o.reshape(B, n_q * dh) @ p["w_o"]))
    j_ffn = jax.jit(lambda x: fd.ffn_swiglu_ref(x, p["mlp_norm"], p["w_gate"],
                                                p["w_up"], p["w_down"]))

    def unfused(x, kc, vc):
        qkv = j_qkv(x); jax.block_until_ready(qkv)
        q = qkv[:n_q].transpose(1, 0, 2)
        kk = qkv[n_q:n_q + n_kv].transpose(1, 0, 2)
        vv = qkv[n_q + n_kv:].transpose(1, 0, 2)
        kc = j_dus(kc, kk[:, None], pos); jax.block_until_ready(kc)
        vc = j_dus(vc, vv[:, None], pos); jax.block_until_ready(vc)
        o = j_attn(q, kc, vc, pos + 1); jax.block_until_ready(o)
        y = j_oproj(x, o); jax.block_until_ready(y)
        return j_ffn(y)

    tf = _timeit(lambda: fused(x, kc, vc)[0])
    tu = _timeit(lambda: unfused(x, kc, vc))
    emit("fig10_decode_layer_fused", tf, f"speedup={tu/tf:.2f}x_vs_unfused")

    # model-level analytic HBM-traffic ratios. Decode is weight/cache-bound
    # (ratio near the paper's low end); prefill/train materialize large
    # activations unfused (the paper's 2-3x regime).
    from repro.configs import get_config
    from repro.core.fusion import model_fusion_report
    cases = [("samba-coe-expert-7b", 8, 4096, 1, "decode"),
             ("mixtral-8x7b", 8, 4096, 1, "decode"),
             ("samba-coe-expert-7b", 8, 4096, 4096, "prefill"),
             ("qwen2.5-32b", 8, 4096, 4096, "prefill")]
    for arch, b, ctx, seq, kind in cases:
        rep = model_fusion_report(get_config(arch), batch=b, ctx=ctx, seq=seq)
        emit(f"fig10_model_{arch}_{kind}", 0.0,
             f"hbm_traffic_ratio={rep.traffic_ratio:.2f}x,"
             f"launch_ratio={rep.launch_ratio:.1f}x")


# ----------------------------------------------------------------------
# Fig 11: kernel-call ratio unfused/fused
# ----------------------------------------------------------------------
def bench_fig11_kernel_calls():
    from repro.configs import get_config
    from repro.core.fusion import model_fusion_report
    cases = [
        ("llama7B-4k-decode", "samba-coe-expert-7b", 8, 4096),
        ("llama7B-4k-prefill", "samba-coe-expert-7b", 8, 1),
        ("mixtral-decode", "mixtral-8x7b", 8, 4096),
        ("qwen32B-decode", "qwen2.5-32b", 8, 32768),
        ("deepseek-decode", "deepseek-v2-lite-16b", 8, 32768),
    ]
    for name, arch, b, ctx in cases:
        rep = model_fusion_report(get_config(arch), batch=b, ctx=ctx)
        emit(f"fig11_{name}", 0.0,
             f"launch_ratio={rep.launch_ratio:.1f}x"
             f"({rep.unfused_kernels}->{rep.fused_kernels})")


# ----------------------------------------------------------------------
# Fig 12 + Table V: CoE latency vs expert count, cross-machine
# ----------------------------------------------------------------------
def bench_fig12_tableV_coe_latency():
    """Fig 12: latency to generate 20 tokens (BS=8) vs the number of experts
    HOSTED on one node. Below HBM capacity all experts are resident; above
    it the LRU misses scale with 1 - resident/hosted (the paper's spike when
    experts spill past HBM). Table V ratios are read off the 150-expert
    point — the Samba-CoE deployment size."""
    from repro.core import DGX_A100, DGX_H100, SN40L_NODE, TPU_V5E_NODE
    from repro.core.bandwidth_model import coe_latency, decode_step_cost

    seven_b = int(7e9)
    bytes_7b = seven_b * 2
    kv_ctx = 2 * 32 * 4096 * 128 * 2          # llama2-7B KV @4k
    hosted_pts = (10, 50, 150, 850)
    n_used = 8                                 # BS=8, distinct experts
    out = {}
    for machine in (SN40L_NODE, DGX_A100, DGX_H100, TPU_V5E_NODE):
        resident_cap = int(machine.hbm.capacity * machine.sockets_per_node
                           * 0.92 // bytes_7b)
        curve = []
        for hosted in hosted_pts:
            resident = min(hosted, resident_cap)
            hit = resident / hosted
            dc = decode_step_cost(seven_b, kv_ctx, n_used, machine)
            lat = coe_latency(n_used, bytes_7b,
                              int(round(n_used * hit)), dc, 20, machine)
            curve.append(lat["total_s"])
        out[machine.name] = curve
        emit(f"fig12_latency_{machine.name}",
             curve[hosted_pts.index(150)] * 1e6,
             "curve_s=" + "/".join("%.3f" % c for c in curve) +
             f"_at_experts={hosted_pts}")
    i150 = hosted_pts.index(150)
    for key, label in (("dgx-a100", "vs_dgx_a100"), ("dgx-h100", "vs_dgx_h100")):
        emit(f"tableV_overall_speedup_{label}", 0.0,
             f"{out[key][i150]/out['sn40l'][i150]:.1f}x_at_150_experts")
    from repro.core.bandwidth_model import switch_cost
    emit("tableV_switch_speedup", 0.0,
         f"vs_a100={switch_cost(bytes_7b, DGX_A100)/switch_cost(bytes_7b, SN40L_NODE):.0f}x,"
         f"vs_h100={switch_cost(bytes_7b, DGX_H100)/switch_cost(bytes_7b, SN40L_NODE):.0f}x")
    # the TPU deployment this framework targets, same workload
    emit("tableV_tpu_v5e_vs_dgx_a100", 0.0,
         f"{out['dgx-a100'][i150]/out['tpu-v5e'][i150]:.1f}x_at_150_experts")


# ----------------------------------------------------------------------
# Fig 13: system footprint vs expert count
# ----------------------------------------------------------------------
def bench_fig13_footprint():
    from repro.core import DGX_A100, DGX_H100, SN40L_NODE
    from repro.core.bandwidth_model import footprint_nodes
    bytes_7b = int(7e9) * 2
    for n in (50, 150, 425, 850):
        sn = footprint_nodes(n, bytes_7b, SN40L_NODE, use_capacity_tier=True)
        da = footprint_nodes(n, bytes_7b, DGX_A100, use_capacity_tier=False)
        dh = footprint_nodes(n, bytes_7b, DGX_H100, use_capacity_tier=False)
        emit(f"fig13_footprint_{n}experts", 0.0,
             f"sn40l={sn},dgx_a100={da},dgx_h100={dh},ratio={da/sn:.0f}x")


# ----------------------------------------------------------------------
# Table IV: decode throughput (tokens/s/user) roofline projections
# ----------------------------------------------------------------------
def bench_tableIV_decode_throughput():
    from repro.configs import get_config
    from repro.core import SN40L_NODE, TPU_V5E_NODE
    from repro.core.bandwidth_model import decode_step_cost
    cases = [("llama31-8b-class", "granite-8b", 16),
             ("llama31-70b-class", "qwen2.5-32b", 16),
             ("llama2-7b-expert", "samba-coe-expert-7b", 8)]
    for name, arch, tp in cases:
        cfg = get_config(arch)
        n = cfg.n_active_params()
        kv_ctx = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 8192 * 2
        for machine in (SN40L_NODE, TPU_V5E_NODE):
            dc = decode_step_cost(n, kv_ctx, 1, machine, tp=tp)
            tput = 1.0 / dc.step_s
            emit(f"tableIV_{name}_{machine.name}", dc.step_s * 1e6,
                 f"tokens/s/user={tput:.0f},bound={dc.bottleneck}")


# ----------------------------------------------------------------------
# Fig 1: measured switch vs execute breakdown on THIS machine
# ----------------------------------------------------------------------
def bench_fig1_switching_measured():
    from repro.configs import get_config, reduced
    from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
    from repro.models import get_model
    from repro.serving import Request, ServingEngine
    cfg = reduced(get_config("samba-coe-expert-7b"))
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    experts = [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
               for i in range(4)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    coe = CompositionOfExperts(HashRouter(4), None, int(2.5 * nbytes))
    for i, h in enumerate(experts):
        coe.register(ExpertHandle(f"e{i}", cfg, h))
    eng = ServingEngine(coe, cfg, max_len=48, n_slots=8, block_size=8)
    rs = np.random.RandomState(0)
    for i in range(8):
        eng.submit(Request(rid=i, tokens=rs.randint(
            0, cfg.vocab_size, (32,)).astype(np.int32), max_new_tokens=8))
    eng.drain()
    st = eng.stats
    exec_s = st.exec_s + st.prefill_s
    total = st.switch_s + exec_s + st.route_s
    emit("fig1_measured_breakdown", total * 1e6,
         f"switch%={100*st.switch_s/total:.1f},exec%={100*exec_s/total:.1f},"
         f"hits={coe.cache.stats.hits},misses={coe.cache.stats.misses}")
    cs = coe.cache.stats
    # copy bandwidth over the full load path (store read + H2D), not the
    # caller-side stall — prefetch hides most of the latter
    bw = cs.bytes_copied_in / max(cs.copy_seconds, 1e-9)
    emit("fig1_measured_copy_bw", cs.copy_seconds * 1e6,
         f"host_to_device_GBps={bw/1e9:.2f},"
         f"stall_s={cs.switch_seconds:.4f},overlap={cs.overlap_ratio:.2f}")


# ----------------------------------------------------------------------
# Fig 12 (measured): switch latency + tokens/s vs expert count + backend
# ----------------------------------------------------------------------
def bench_sweep_switching(tiny: bool = False):
    """Measured Fig-12 companion to the analytic ``fig12`` rows: sweep the
    number of hosted experts and the capacity-tier backend (host DRAM,
    mmap-on-disk, int8-quantized) with the HBM tier pinned to ~1.5 experts,
    so every switch must reload from the store. ``mode=async`` runs the
    double-buffered prefetch pipeline (next group's expert loads during the
    current group's decode); ``mode=cold`` disables prefetch — the
    cold-reload baseline where the whole store-read + H2D copy sits on the
    critical path. ``overlap_ratio`` compares per-switch stalls where the
    modes actually differ — async's stall per *prefetched* switch vs cold's
    stall per miss — because each generate() pass opens with one
    unavoidable cold miss in BOTH modes, and a total-stall ratio would let
    that shared term drown the signal at small sweep sizes. Emits
    ``results/bench_switching.json`` (rows + a flat ``metrics`` dict that
    ``tools/check_bench.py`` gates CI on)."""
    import shutil
    import tempfile

    from repro.configs import get_config, reduced
    from repro.core import CompositionOfExperts, ExpertHandle
    from repro.models import get_model
    from repro.store import make_store

    class FirstTokenRouter:
        def __init__(self, n):
            self.n = n

        def route(self, params, tokens):
            return jnp.asarray(np.asarray(tokens)[:, 0] % self.n)

    cfg = reduced(get_config("samba-coe-expert-7b"))
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    counts = [2, 3] if tiny else [2, 4, 6]
    per_expert = 1 if tiny else 2            # prompts per expert group
    n_tokens = 6 if tiny else 12
    rounds = 2                               # timed generate() passes
    S = 8
    hosts = [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
             for i in range(max(counts))]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(hosts[0]))
    backends = ["host", "mmap", "int8"]

    rs = np.random.RandomState(0)
    rows, metrics = [], {}
    tmp = tempfile.mkdtemp(prefix="bench-switching-")
    try:
        for backend in backends:
            for n in counts:
                prompts = rs.randint(0, cfg.vocab_size,
                                     (n * per_expert, S)).astype(np.int32)
                prompts[:, 0] = np.arange(n * per_expert) % n
                per_switch = {}
                for mode in ("async", "cold"):
                    store = make_store(
                        backend, root=f"{tmp}/{backend}-{n}-{mode}")
                    coe = CompositionOfExperts(
                        FirstTokenRouter(n), None, int(1.5 * nbytes),
                        store=store)
                    for i in range(n):
                        coe.register(ExpertHandle(f"e{i}", cfg, hosts[i]))
                    prefetch = mode == "async"
                    coe.generate(prompts, 2, prefetch_next=prefetch)  # warmup
                    for e in coe.cache.expert_ids():
                        coe.cache.drop(e)
                    coe.cache.stats.reset()
                    t0 = time.perf_counter()
                    for _ in range(rounds):
                        coe.generate(prompts, n_tokens,
                                     prefetch_next=prefetch)
                    wall = time.perf_counter() - t0
                    coe.cache.close()
                    st = coe.cache.stats
                    tps = rounds * prompts.shape[0] * n_tokens / wall
                    switches = st.hits + st.misses
                    # the per-switch stall where the modes differ: async is
                    # judged on its prefetched switches, cold on its misses
                    if mode == "async" and st.prefetch_hits:
                        per_switch[mode] = (st.stall_prefetch_seconds
                                            / st.prefetch_hits)
                    else:
                        per_switch[mode] = (st.stall_miss_seconds
                                            / max(st.misses, 1))
                    rows.append({
                        "backend": backend, "n_experts": n, "mode": mode,
                        "wall_s": wall, "tokens_per_s": tps,
                        "switches": switches,
                        "switch_stall_s": st.switch_seconds,
                        "stall_miss_s": st.stall_miss_seconds,
                        "stall_prefetch_s": st.stall_prefetch_seconds,
                        "stall_failed_prefetch_s":
                            st.stall_failed_prefetch_seconds,
                        "prefetch_failures": st.prefetch_failures,
                        "stall_per_switch_ms": 1e3 * per_switch[mode],
                        "store_read_s": st.store_read_seconds,
                        "h2d_s": st.h2d_seconds,
                        "pipeline_overlap": st.overlap_ratio,
                        "misses": st.misses,
                        "prefetch_hits": st.prefetch_hits,
                        "evictions": st.evictions,
                        "expert_hbm_bytes": nbytes,
                        "expert_stored_bytes": store.stored_bytes("e0"),
                    })
                    emit(f"sweep_switching_{backend}_n{n}_{mode}",
                         wall * 1e6,
                         f"tokens/s={tps:.1f},"
                         f"stall_ms={st.switch_seconds*1e3:.1f},"
                         f"stall_per_switch_ms={per_switch[mode]*1e3:.1f},"
                         f"read_ms={st.store_read_seconds*1e3:.1f},"
                         f"h2d_ms={st.h2d_seconds*1e3:.1f},"
                         f"prefetch_hits={st.prefetch_hits}")
                overlap = (1.0 - per_switch["async"] / per_switch["cold"]
                           if per_switch["cold"] > 0 else 0.0)
                metrics[f"switching:{backend}:n{n}:overlap_ratio"] = overlap
                a = next(r for r in rows if r["backend"] == backend
                         and r["n_experts"] == n and r["mode"] == "async")
                c = next(r for r in rows if r["backend"] == backend
                         and r["n_experts"] == n and r["mode"] == "cold")
                metrics[f"switching:{backend}:n{n}:tps_async_vs_cold"] = (
                    a["tokens_per_s"] / c["tokens_per_s"])
                emit(f"sweep_switching_{backend}_n{n}_overlap", 0.0,
                     f"overlap_ratio={overlap:.2f}_vs_cold_reload")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    doc = {"schema": 1,
           "config": {"arch": "samba-coe-expert-7b(reduced)",
                      "expert_counts": counts, "backends": backends,
                      "per_expert_prompts": per_expert,
                      "n_tokens": n_tokens, "rounds": rounds,
                      "hbm_capacity_experts": 1.5, "tiny": tiny},
           "rows": rows, "metrics": _gated_metrics(metrics)}
    (_results_dir() / "bench_switching.json").write_text(
        json.dumps(doc, indent=1))


# ----------------------------------------------------------------------
# Arrival-rate sweep: run-to-completion vs continuous batching (§VI-C)
# ----------------------------------------------------------------------
def bench_sweep_arrival(tiny: bool = False, backend: str = "both"):
    """Offered-load sweep over the serving engine. One Poisson request trace
    per offered rate (requests/s; ``inf`` = burst, every request queued at
    t=0) is replayed against BOTH schedulers on the same paged KV substrate
    and the same compiled step functions — the measured difference is pure
    scheduling. Emits achieved tokens/s and p50/p99 request latency; the
    final row is the continuous/run-to-completion throughput ratio at the
    highest offered load (the paper's keep-the-chip-busy claim).

    A second, fused-vs-unfused axis (the Fig-6 analogue) replays one fixed
    burst through the serving backends selected by ``backend`` ('xla' /
    'fused' / 'both'): per backend it records achieved tokens/s, the
    measured HBM traffic of one compiled decode step, and the measured
    operational intensity next to ``core/fusion.py``'s predictions. These
    runs use float32 weights and KV (the backends' strict-parity dtype —
    see ``serving/backends.py``), so with ``backend='both'`` the greedy
    token streams are asserted identical across backends.

    Every replayed request carries generous TTFT+TPOT deadlines, so the
    sweep also reports **goodput** (SLO-met tokens/s, ``obs.slo``) and SLO
    attainment per scheduler and per backend: on a healthy engine nearly
    every request meets the deadlines and goodput tracks throughput; a
    scheduling collapse (queueing wedge, stalled decode) turns the missed
    deadlines into a goodput drop the CI gate catches even when raw
    tokens/s survives."""
    import hashlib

    from repro.configs import get_config, reduced
    from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
    from repro.core.fusion import backend_prediction
    from repro.models import get_model
    from repro.obs.slo import request_slo_met
    from repro.serving import Request, ServingEngine
    from repro.serving.backends import fused_kernel_hbm_bytes

    cfg = reduced(get_config("samba-coe-expert-7b"))
    # generous deadlines relative to the tiny sweep's measured latencies
    # (ttft_p99 ~0.06s, per-token ~10ms): headroom for CI jitter, tight
    # enough that a structural stall blows them
    slo_ttft, slo_tpot = 1.0, 0.5
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    n_exp = 3
    experts = [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
               for i in range(n_exp)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))

    def mk_engine(scheduler, runner=None):
        coe = CompositionOfExperts(HashRouter(n_exp), None, int(2.5 * nbytes))
        for i, h in enumerate(experts):
            coe.register(ExpertHandle(f"e{i}", cfg, h))
        return ServingEngine(coe, cfg, max_len=32, n_slots=4, block_size=8,
                             scheduler=scheduler, runner=runner)

    # one fixed trace per offered load: (arrival offset s, prompt, max_new).
    # decode-heavy mix (short prompts, long + uneven outputs): the regime
    # where scheduling — not prefill — decides throughput (§VI-C decode).
    rs = np.random.RandomState(0)
    n_req = 8 if tiny else 20
    prompts = [rs.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
               for _ in range(n_req)]
    new_toks = [int(rs.randint(4, 23)) for _ in range(n_req)]
    loads = [float("inf")] if tiny else [4.0, 12.0, float("inf")]
    repeats = 2 if tiny else 3
    # wall time is noisy on shared machines: best-of-N,
    # schedulers alternated within each repeat
    traces = {}
    for lam in loads:
        if np.isinf(lam):
            offs = np.zeros(n_req)
        else:
            offs = np.cumsum(rs.exponential(1.0 / lam, n_req))
        traces[lam] = list(zip(offs, prompts, new_toks))

    def serve_trace(eng, trace):
        pending = list(trace)
        done = []
        t0 = time.perf_counter()
        rid = 0
        while pending or eng.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                off, toks, n_new = pending.pop(0)
                r = Request(rid=rid, tokens=toks, max_new_tokens=n_new,
                            slo_ttft_s=slo_ttft, slo_tpot_s=slo_tpot)
                r.arrival_s = t0 + off   # offered arrival, not submit time:
                eng.submit(r)            # queueing delay while the engine is
                rid += 1                 # mid-step must count in latency
            if not eng.has_work and pending:
                time.sleep(min(pending[0][0] - now, 0.05))
                continue
            done.extend(eng.step())
        return done, time.perf_counter() - t0

    shared_runner = None
    best = {}                       # (sched, lam) -> dict of the fastest run
    for lam in loads:
        for rep in range(repeats):
            for sched in ("run_to_completion", "continuous"):
                eng = mk_engine(sched, runner=shared_runner)
                shared_runner = eng.runner    # share the compile cache
                # AOT-compile every prefill bucket + the decode step
                # outside the timed window (a mid-burst bucket compile
                # would otherwise land in the TTFT percentiles)
                eng.warmup()
                eng.submit(Request(rid=10_000, tokens=np.zeros(10, np.int32),
                                   max_new_tokens=2))
                eng.drain()
                eng.stats.reset()
                done, wall = serve_trace(eng, traces[lam])
                lat = np.array([r.latency_s for r in done])
                ttft = np.array([r.first_token_s - r.arrival_s
                                 for r in done])
                met = [r for r in done if request_slo_met(r)]
                run = {"wall": wall,
                       "tps": sum(r.max_new_tokens for r in done) / wall,
                       "p50": np.percentile(lat, 50), "p99": np.percentile(lat, 99),
                       "ttft_p50": np.percentile(ttft, 50),
                       "ttft_p99": np.percentile(ttft, 99),
                       "goodput": sum(r.max_new_tokens for r in met) / wall,
                       "attain": len(met) / len(done),
                       "occ": eng.stats.mean_occupancy,
                       "switches": eng.stats.switches}
                key = (sched, lam)
                if key not in best:
                    best[key] = run
                else:       # per-metric best across repeats: a repeat can win
                    b = best[key]   # on tps while a hiccup inflates its p99
                    b["tps"] = max(b["tps"], run["tps"])
                    b["wall"] = min(b["wall"], run["wall"])
                    b["p50"] = min(b["p50"], run["p50"])
                    b["p99"] = min(b["p99"], run["p99"])
                    b["ttft_p50"] = min(b["ttft_p50"], run["ttft_p50"])
                    b["ttft_p99"] = min(b["ttft_p99"], run["ttft_p99"])
                    b["goodput"] = max(b["goodput"], run["goodput"])
                    b["attain"] = max(b["attain"], run["attain"])
                    b["occ"] = max(b["occ"], run["occ"])
                    b["switches"] = min(b["switches"], run["switches"])
    for sched in ("run_to_completion", "continuous"):
        for lam in loads:
            b = best[(sched, lam)]
            label = "inf" if np.isinf(lam) else f"{lam:g}"
            emit(f"sweep_{sched}_load_{label}", b["wall"] * 1e6,
                 f"tokens/s={b['tps']:.1f},p50_ms={b['p50']*1e3:.0f},"
                 f"p99_ms={b['p99']*1e3:.0f},"
                 f"ttft_p99_ms={b['ttft_p99']*1e3:.0f},"
                 f"goodput={b['goodput']:.1f},"
                 f"slo_attainment={b['attain']:.2f},"
                 f"occupancy={b['occ']:.2f},"
                 f"switches={b['switches']},best_of={repeats}")
    hi = loads[-1]
    ratio = best[("continuous", hi)]["tps"] / best[("run_to_completion", hi)]["tps"]
    emit("sweep_continuous_vs_rtc_highest_load", 0.0,
         f"throughput_ratio={ratio:.2f}x_at_burst")

    # ---- fused-vs-unfused axis (Fig-6 analogue) -------------------------
    # float32 weights + KV: the backends' strict-parity dtype, so greedy
    # token streams must be identical across backends (bf16 parity is
    # fp-tolerance only — the XLA body rounds every op boundary to bf16
    # while the fused kernels keep activations f32 in VMEM)
    backends = {"xla": ["xla"], "fused": ["fused"],
                "both": ["xla", "fused"]}[backend]
    f32 = lambda t: jax.tree.map(
        lambda x: np.asarray(x, np.float32)
        if x.dtype == jnp.bfloat16 else np.asarray(x), t)
    experts32 = [f32(e) for e in experts]
    nbytes32 = sum(x.nbytes for x in jax.tree.leaves(experts32[0]))
    n_freq = 6 if tiny else 12
    fus_trace = [(rs.randint(0, cfg.vocab_size, (10,)).astype(np.int32),
                  int(rs.randint(6, 18))) for _ in range(n_freq)]

    fus_rows, digests = [], {}
    for bk in backends:
        coe = CompositionOfExperts(HashRouter(n_exp), None,
                                   int(2.5 * nbytes32))
        for i, h in enumerate(experts32):
            coe.register(ExpertHandle(f"e{i}", cfg, h))
        eng = ServingEngine(coe, cfg, max_len=32, n_slots=4, block_size=8,
                            backend=bk, kv_dtype=jnp.float32)
        # warm the compile cache (all prefill buckets + the decode step)
        # outside the timed window
        eng.warmup()
        eng.submit(Request(rid=10_000, tokens=np.zeros(10, np.int32),
                           max_new_tokens=2))
        eng.drain()
        eng.stats.reset()
        t0 = time.perf_counter()
        for rid, (toks, n_new) in enumerate(fus_trace):
            eng.submit(Request(rid=rid, tokens=toks, max_new_tokens=n_new,
                               slo_ttft_s=slo_ttft, slo_tpot_s=slo_tpot))
        fdone = eng.drain()
        wall = time.perf_counter() - t0
        tps = sum(r.max_new_tokens for r in fdone) / wall
        fmet = [r for r in fdone if request_slo_met(r)]
        fgoodput = sum(r.max_new_tokens for r in fmet) / wall
        outs = {r.rid: r.output for r in fdone}
        digests[bk] = hashlib.sha256(
            b"".join(outs[i].tobytes() for i in sorted(outs))).hexdigest()[:16]

        # measured HBM traffic of one compiled (n_slots, 1) decode step.
        # xla: the compiled step's XLA cost model. fused: XLA treats Pallas
        # calls as opaque (and the CPU interpret-mode lowering inflates
        # them), so the exact DMA accounting of the kernels' grid x
        # BlockSpec tiles is used instead — the step is kernel-dominated
        # (only the K/V scatter and embed/head stay outside them)
        B, ctx = eng.n_slots, eng.max_blocks * eng.block
        if bk == "fused":
            step_bytes = float(fused_kernel_hbm_bytes(
                cfg, B, eng.max_blocks, eng.block, kv_itemsize=4,
                p_itemsize=4, act_itemsize=4))
            measurement = "pallas_dma_accounting"
        else:
            cost = eng.runner.step_cost_analysis((eng.n_slots, 1)) or {}
            step_bytes = float(cost.get("bytes accessed", 0.0))
            measurement = "xla_cost_analysis"
        pred = backend_prediction(cfg, B, ctx, bk, dtype_bytes=4)
        intensity = pred["flops"] / step_bytes if step_bytes else 0.0
        fus_rows.append({
            "backend": bk, "tokens_per_s": tps, "wall_s": wall,
            "goodput_tok_s": fgoodput,
            "slo_attainment": len(fmet) / len(fdone),
            "measured_step_bytes": step_bytes,
            "measured_intensity": intensity,
            "measurement": measurement,
            "predicted_step_bytes": pred["predicted_hbm_bytes"],
            "predicted_intensity": pred["predicted_intensity"],
            "flops_per_step": pred["flops"],
            "token_digest": digests[bk]})
        emit(f"sweep_fusion_{bk}", wall * 1e6,
             f"tokens/s={tps:.1f},goodput={fgoodput:.1f},"
             f"slo_attainment={len(fmet) / len(fdone):.2f},"
             f"measured_MB_per_step={step_bytes/1e6:.2f},"
             f"measured_intensity={intensity:.1f},"
             f"predicted_intensity={pred['predicted_intensity']:.1f}")
    if len(backends) == 2:
        if digests["xla"] != digests["fused"]:
            raise AssertionError(
                "fused backend diverged from xla greedy token streams "
                f"(digest {digests['fused']} != {digests['xla']})")
        emit("sweep_fusion_parity", 0.0,
             f"tokens_identical=1,digest={digests['xla']}")

    rows = []
    for (sched, lam), b in best.items():
        rows.append({"scheduler": sched,
                     "offered_load": "inf" if np.isinf(lam) else lam,
                     "wall_s": b["wall"], "tokens_per_s": b["tps"],
                     "p50_s": float(b["p50"]), "p99_s": float(b["p99"]),
                     "ttft_p50_s": float(b["ttft_p50"]),
                     "ttft_p99_s": float(b["ttft_p99"]),
                     "goodput_tok_s": float(b["goodput"]),
                     "slo_attainment": float(b["attain"]),
                     "occupancy": b["occ"], "switches": b["switches"],
                     "best_of": repeats})
    metrics = {
        "arrival:continuous:tps@burst": best[("continuous", hi)]["tps"],
        "arrival:continuous_vs_rtc_ratio": ratio,
        "arrival:continuous:p99_s@burst": best[("continuous", hi)]["p99"],
        "arrival:continuous:ttft_p99_s@burst":
            float(best[("continuous", hi)]["ttft_p99"]),
        "arrival:continuous:goodput@burst":
            float(best[("continuous", hi)]["goodput"]),
        "arrival:continuous:slo_attainment@burst":
            float(best[("continuous", hi)]["attain"]),
    }
    if "fused" in digests:
        frow = next(r for r in fus_rows if r["backend"] == "fused")
        metrics["arrival:fused:tps@burst"] = frow["tokens_per_s"]
        metrics["arrival:fused:measured_intensity"] = \
            frow["measured_intensity"]
    if len(backends) == 2:
        xrow = next(r for r in fus_rows if r["backend"] == "xla")
        metrics["arrival:fused:tokens_identical"] = 1.0
        metrics["arrival:fused:intensity_ratio"] = (
            frow["measured_intensity"] / xrow["measured_intensity"]
            if xrow["measured_intensity"] else 0.0)
    doc = {"schema": 1,
           "config": {"arch": "samba-coe-expert-7b(reduced)",
                      "n_requests": n_req, "repeats": repeats,
                      "loads": ["inf" if np.isinf(l) else l for l in loads],
                      "slo": {"ttft_s": slo_ttft, "tpot_s": slo_tpot},
                      "tiny": tiny, "backend_axis": backends},
           "rows": rows,
           "fusion_axis": {"dtype": "float32", "n_requests": n_freq,
                           "rows": fus_rows},
           "metrics": _gated_metrics(metrics)}
    (_results_dir() / "bench_arrival.json").write_text(
        json.dumps(doc, indent=1))


# ----------------------------------------------------------------------
# Node sweep: tokens/s + latency vs socket-group shape (Table V analogue)
# ----------------------------------------------------------------------
def bench_sweep_node(tiny: bool = False):
    """Multi-socket node sweep over socket-group shapes (TP x groups: 8x1,
    4x2, 2x4, 1x8) on 8 emulated CPU sockets, at saturating offered load
    (every request queued at t=0) — the Table V footprint/throughput
    analogue. One fixed request trace and one expert set (padded once for
    TP=8 so every shape runs the *same* model) replay against each shape;
    total decode slots are held constant across shapes. Reports achieved
    tokens/s, p50/p99 latency, inter-group imbalance and switch stalls, and
    emits ``results/bench_node.json`` with flat metrics — the headline is
    ``node:multi_vs_1group_tps``, multi-group throughput over the single
    TP=8 group, which must stay strictly above 1 (gated in CI)."""
    _ensure_host_devices(8)    # covers --sweep-node AND --only sweep_node
    from repro.configs import get_config, pad_for_tp, reduced
    from repro.core import HashRouter
    from repro.models import get_model
    from repro.node import make_node_topology, RDUNode
    from repro.serving import Request

    shapes = [(8, 1), (4, 2), (2, 4), (1, 8)]
    n_exp = 4 if tiny else 6
    n_req = 12 if tiny else 32
    total_slots = 8
    S = 8
    cfg = pad_for_tp(reduced(get_config("samba-coe-expert-7b")), 8)
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    experts = [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
               for i in range(n_exp)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))
    rs = np.random.RandomState(0)
    trace = [(i, rs.randint(0, cfg.vocab_size, (S,)).astype(np.int32),
              int(rs.randint(4, 9 if tiny else 13))) for i in range(n_req)]

    rows, metrics = [], {}
    for tp, n_groups in shapes:
        topo = make_node_topology(tp, n_groups)
        node = RDUNode(topo, cfg, HashRouter(n_exp), None,
                       group_hbm_bytes=int(3.0 * nbytes),
                       group_kv_reserve_bytes=int(0.8 * nbytes),
                       n_slots=max(1, total_slots // n_groups),
                       block_size=8, max_len=S + (16 if tiny else 20))
        for i, h in enumerate(experts):
            node.register_expert(f"e{i}", h)
        placement = node.plan()
        # warm every group's compile cache outside the timed window
        for w, gs in enumerate(node.groups):
            gs.engine.submit(Request(
                rid=100_000 + w, tokens=np.zeros(S, np.int32),
                max_new_tokens=2, expert=node.expert_names()[0]))
        node.drain()
        for gs in node.groups:
            gs.engine.stats.reset()
            gs.coe.cache.stats.reset()
            gs.submitted = 0
        node.route_s = 0.0

        t0 = time.perf_counter()
        for rid, toks, n_new in trace:
            node.submit(Request(rid=rid, tokens=toks, max_new_tokens=n_new))
        done = node.drain()
        wall = time.perf_counter() - t0
        node.close()
        st = node.stats()
        lat = np.array([r.latency_s for r in done])
        tps = st.tokens_out / wall
        name = topo.name
        rows.append({
            "shape": name, "tp": tp, "n_groups": n_groups,
            "wall_s": wall, "tokens_per_s": tps,
            "p50_s": float(np.percentile(lat, 50)),
            "p99_s": float(np.percentile(lat, 99)),
            "imbalance": st.imbalance,
            "switch_stall_s": st.switch_stall_s,
            "starvation_overrides": st.starvation_overrides,
            "spilled_experts": len(placement.spilled),
            "group_hbm_bytes": int(3.0 * nbytes),
            "resident_experts_per_group":
                node.groups[0].coe.hbm_budget.resident_experts(nbytes),
            "per_group_tokens": [g["tokens_out"] for g in st.per_group],
        })
        metrics[f"node:{name}:tokens_per_s"] = tps
        emit(f"sweep_node_{name}", wall * 1e6,
             f"tokens/s={tps:.1f},p50_ms={rows[-1]['p50_s']*1e3:.0f},"
             f"p99_ms={rows[-1]['p99_s']*1e3:.0f},"
             f"imbalance={st.imbalance:.2f},"
             f"stall_ms={st.switch_stall_s*1e3:.0f}")

    one_group = next(r for r in rows if r["n_groups"] == 1)
    multi_best = max((r for r in rows if r["n_groups"] > 1),
                     key=lambda r: r["tokens_per_s"])
    ratio = multi_best["tokens_per_s"] / one_group["tokens_per_s"]
    metrics["node:multi_vs_1group_tps"] = ratio
    emit("sweep_node_multi_vs_1group", 0.0,
         f"throughput_ratio={ratio:.2f}x_best={multi_best['shape']}"
         f"_vs_{one_group['shape']}_at_burst")

    doc = {"schema": 1,
           "config": {"arch": "samba-coe-expert-7b(reduced,pad_tp8)",
                      "shapes": [f"{t}x{g}" for t, g in shapes],
                      "n_experts": n_exp, "n_requests": n_req,
                      "total_slots": total_slots, "tiny": tiny},
           "rows": rows, "metrics": _gated_metrics(metrics)}
    (_results_dir() / "bench_node.json").write_text(json.dumps(doc, indent=1))


# ----------------------------------------------------------------------
# Prefill sweep: AOT bucketed packed prefill + prefill/decode disaggregation
# ----------------------------------------------------------------------
def bench_sweep_prefill(tiny: bool = False):
    """Two axes around the prefill path (``serving/prefill.py``).

    Axis A (single engine): one mixed-length burst — every prompt length
    DISTINCT, the worst case for a compile-per-shape prefill — replayed
    against ``prefill_mode='packed'`` (power-of-two buckets AOT-compiled at
    ``warmup()``, multiple prompts packed per forward) and
    ``prefill_mode='sequential'`` (one ``prefill_kv`` jit per novel
    length). TTFT is first-token time minus offered arrival (t=0 for the
    whole burst, so queueing counts). Sequential pays a fresh XLA compile
    for nearly every request; packed must pay ZERO after warmup — the
    ``record_compile`` hook counts them and CI gates the count at exactly 0.

    Axis B (8 emulated sockets): the same burst against a DISAGGREGATED
    node (1 dedicated prefill group handing KV blocks off to 3 decode
    groups) and a colocated node (4 decode groups prefill for themselves).
    The handoff moves prefilled KV blocks byte-for-byte, so the greedy
    token streams must be IDENTICAL — gated via a sha256 digest over all
    outputs."""
    _ensure_host_devices(8)
    import hashlib

    from repro.configs import get_config, reduced
    from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
    from repro.models import get_model
    from repro.node import make_node_topology, RDUNode
    from repro.serving import Request, ServingEngine
    from repro.serving.prefill import compile_count, reset_compile_counts

    cfg = reduced(get_config("samba-coe-expert-7b"))
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    n_exp = 2 if tiny else 3
    experts = [jax.tree.map(np.asarray, m.init(jax.random.fold_in(rng, i)))
               for i in range(n_exp)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))

    rs = np.random.RandomState(0)
    n_req = 8 if tiny else 18
    lengths = rs.permutation(np.arange(4, 4 + n_req))
    burst = [(rs.randint(0, cfg.vocab_size, (int(L),)).astype(np.int32),
              int(rs.randint(3, 7))) for L in lengths]
    max_len = int(lengths.max()) + 8 + 8      # prompt + max_new + slack

    def mk_engine(mode):
        coe = CompositionOfExperts(HashRouter(n_exp), None, int(2.5 * nbytes))
        for i, h in enumerate(experts):
            coe.register(ExpertHandle(f"e{i}", cfg, h))
        return ServingEngine(coe, cfg, max_len=max_len, n_slots=4,
                             block_size=8, prefill_mode=mode)

    def replay(submit, drain):
        t0 = time.perf_counter()
        for rid, (toks, n_new) in enumerate(burst):
            r = Request(rid=rid, tokens=toks, max_new_tokens=n_new)
            r.arrival_s = t0                 # burst: all offered at t=0
            submit(r)
        done = drain()
        wall = time.perf_counter() - t0
        ttft = np.array([r.first_token_s - r.arrival_s for r in done])
        return done, wall, ttft

    rows, metrics = [], {}
    repeats = 2
    best = {}
    for mode in ("packed", "sequential"):
        for _ in range(repeats):
            eng = mk_engine(mode)
            eng.warmup()
            reset_compile_counts()
            _, wall, ttft = replay(eng.submit, eng.drain)
            run = {"wall": wall, "compiles": compile_count(),
                   "ttft_p50": float(np.percentile(ttft, 50)),
                   "ttft_p99": float(np.percentile(ttft, 99))}
            if mode not in best:
                best[mode] = run
            else:           # best-of-N: wall noise must not gate CI
                b = best[mode]
                b["wall"] = min(b["wall"], run["wall"])
                b["ttft_p50"] = min(b["ttft_p50"], run["ttft_p50"])
                b["ttft_p99"] = min(b["ttft_p99"], run["ttft_p99"])
                b["compiles"] = max(b["compiles"], run["compiles"])
        b = best[mode]
        rows.append({"axis": "packed_vs_sequential", "mode": mode,
                     "wall_s": b["wall"], "n_requests": n_req,
                     "ttft_p50_s": b["ttft_p50"],
                     "ttft_p99_s": b["ttft_p99"],
                     "recompiles_after_warmup": b["compiles"],
                     "best_of": repeats})
        emit(f"sweep_prefill_{mode}", b["wall"] * 1e6,
             f"ttft_p50_ms={b['ttft_p50']*1e3:.0f},"
             f"ttft_p99_ms={b['ttft_p99']*1e3:.0f},"
             f"recompiles_after_warmup={b['compiles']}")
    ratio = best["sequential"]["ttft_p99"] / best["packed"]["ttft_p99"]
    metrics["prefill:packed:recompiles_after_warmup"] = \
        float(best["packed"]["compiles"])
    metrics["prefill:packed:ttft_p99_s@burst"] = best["packed"]["ttft_p99"]
    metrics["prefill:packed_vs_seq_ttft_p99"] = ratio
    emit("sweep_prefill_packed_vs_seq", 0.0,
         f"ttft_p99_ratio={ratio:.2f}x_at_burst")

    # ---- axis B: disaggregated vs colocated node ------------------------
    digests = {}
    for mode, n_pref in (("disagg", 1), ("colocated", 0)):
        topo = make_node_topology(1, 4)
        node = RDUNode(topo, cfg, HashRouter(n_exp), None,
                       group_hbm_bytes=int(3.0 * nbytes),
                       group_kv_reserve_bytes=int(0.8 * nbytes),
                       prefill_groups=n_pref,
                       n_slots=4, block_size=8, max_len=max_len)
        for i, h in enumerate(experts):
            node.register_expert(f"e{i}", h)
        node.warmup()
        reset_compile_counts()
        done, wall, ttft = replay(node.submit, node.drain)
        compiles = compile_count()
        within = node.hbm_within_budget()
        node.close()
        outs = {r.rid: r.output for r in done}
        digests[mode] = hashlib.sha256(
            b"".join(outs[i].tobytes() for i in sorted(outs))).hexdigest()[:16]
        rows.append({"axis": "disagg_vs_colocated", "mode": mode,
                     "wall_s": wall, "n_requests": n_req,
                     "ttft_p50_s": float(np.percentile(ttft, 50)),
                     "ttft_p99_s": float(np.percentile(ttft, 99)),
                     "recompiles_after_warmup": compiles,
                     "hbm_within_budget": within,
                     "token_digest": digests[mode]})
        emit(f"sweep_prefill_node_{mode}", wall * 1e6,
             f"ttft_p50_ms={np.percentile(ttft, 50)*1e3:.0f},"
             f"ttft_p99_ms={np.percentile(ttft, 99)*1e3:.0f},"
             f"recompiles_after_warmup={compiles},"
             f"digest={digests[mode]}")
    identical = float(digests["disagg"] == digests["colocated"])
    if not identical:
        raise AssertionError(
            "disaggregated node diverged from colocated greedy token "
            f"streams (digest {digests['disagg']} != {digests['colocated']})")
    metrics["prefill:disagg:tokens_identical"] = identical
    emit("sweep_prefill_disagg_parity", 0.0,
         f"tokens_identical={int(identical)},digest={digests['disagg']}")

    doc = {"schema": 1,
           "config": {"arch": "samba-coe-expert-7b(reduced)",
                      "n_requests": n_req, "n_experts": n_exp,
                      "prompt_lengths": [int(x) for x in lengths],
                      "repeats": repeats, "tiny": tiny},
           "rows": rows, "metrics": _gated_metrics(metrics)}
    (_results_dir() / "bench_prefill.json").write_text(
        json.dumps(doc, indent=1))


# ----------------------------------------------------------------------
# Tenancy sweep: prefix sharing + sessions vs re-prefill-everything
# ----------------------------------------------------------------------
def bench_sweep_tenancy(tiny: bool = False):
    """Trace-replay tenancy sweep: a population of multi-turn sessions with
    a realistic prompt-share distribution (70% of sessions open with one of
    a handful of per-tenant system prompts; turn counts mixed 1-3; each
    turn re-sends the whole conversation plus fresh user tokens) is
    replayed wave-by-wave against TWO engines on identical traffic:

      * ``shared``   — ``prefix_sharing=True``: the PrefixIndex dedups the
        system prompts across sessions, SessionManager retention hands each
        session's history KV to its next turn, and hits prefill only the
        un-shared suffix;
      * ``unshared`` — the baseline engine, which re-prefills every prompt
        token of every turn.

    Measures shared-vs-unshared TTFT p99, the fraction of prompt tokens
    whose prefill was avoided, and KV bytes deduplicated. float32 weights
    and KV (the strict-parity dtype): the greedy token streams of the two
    engines are asserted byte-identical — sharing must change WHERE bytes
    live, never WHAT tokens come out. After each shared run the retained
    state is released and the pool is asserted fully recycled (zero leaked
    blocks). A final ungated pass drives the shared engine through
    ``StreamingFrontend`` (per-tenant quotas + streaming callbacks)."""
    import hashlib

    from repro.configs import get_config, reduced
    from repro.core import CompositionOfExperts, ExpertHandle, HashRouter
    from repro.models import get_model
    from repro.serving import (QuotaExceeded, Request, ServingEngine,
                               StreamingFrontend, TenantQuota)

    cfg = reduced(get_config("samba-coe-expert-7b"))
    m = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    n_exp = 2
    f32 = lambda t: jax.tree.map(
        lambda x: np.asarray(x, np.float32)
        if x.dtype == jnp.bfloat16 else np.asarray(x), t)
    experts = [f32(jax.tree.map(np.asarray,
                                m.init(jax.random.fold_in(rng, i))))
               for i in range(n_exp)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(experts[0]))

    max_len = 256
    n_slots = 4 if tiny else 8
    n_sessions = 12 if tiny else 30
    repeats = 2

    # ---- the session trace (fixed across engines and repeats) -----------
    # 70% of sessions open with one of two shared system prompts; the rest
    # carry a private prompt of the same length. User turns append 8-24
    # fresh tokens; turn counts cycle 1/2/3 (mean 2 — short-chat regime).
    # The 96-token system prompt keeps prefill compute (what sharing
    # avoids) the dominant TTFT term even at reduced model scale.
    rs = np.random.RandomState(7)
    SYS = 96
    sys_prompts = [rs.randint(1, cfg.vocab_size, (SYS,)).astype(np.int32)
                   for _ in range(2)]
    sessions = []
    for s in range(n_sessions):
        shared_sys = s < int(round(0.7 * n_sessions))
        sysp = (sys_prompts[s % 2] if shared_sys
                else rs.randint(1, cfg.vocab_size, (SYS,)).astype(np.int32))
        turns = 1 + (s % 3)
        sessions.append({
            "sid": f"s{s}", "expert": f"e{s % n_exp}", "sys": sysp,
            "turns": turns, "shared_sys": shared_sys,
            "user": [rs.randint(1, cfg.vocab_size,
                                (int(rs.randint(8, 25)),)).astype(np.int32)
                     for _ in range(turns)],
            # short replies keep the TTFT tail prefill-bound (what
            # sharing avoids) rather than decode-queueing-bound
            "new": [int(rs.randint(2, 6)) for _ in range(turns)]})
    max_turns = max(s["turns"] for s in sessions)

    def mk_engine(sharing: bool) -> ServingEngine:
        coe = CompositionOfExperts(HashRouter(n_exp), None,
                                   int(2.5 * nbytes))
        for i, h in enumerate(experts):
            coe.register(ExpertHandle(f"e{i}", cfg, h))
        return ServingEngine(coe, cfg, max_len=max_len, n_slots=n_slots,
                             block_size=8, prefix_sharing=sharing,
                             kv_dtype=jnp.float32)

    def replay(sharing: bool):
        eng = mk_engine(sharing)
        eng.warmup()
        # primer: serve one request per shared system prompt per expert so
        # the timed waves hit a WARM index — steady-state serving, not a
        # cold start (run on both engines: same compiles, same cache state)
        for j, sp in enumerate(sys_prompts):
            for e in range(n_exp):
                eng.submit(Request(
                    rid=900_000 + j * n_exp + e,
                    tokens=np.concatenate([sp, np.asarray([j + 1], np.int32)]),
                    max_new_tokens=2, expert=f"e{e}"))
        eng.drain()
        eng.stats.reset()
        hit0 = eng.stats.prefix_hit_tokens      # reset() zeroes; belt+braces
        cow0 = eng.pool.stats.cow_splits

        outs, ttfts = {}, []
        history = {}                 # sid -> committed conversation tokens
        prompt_tokens = 0
        t0 = time.perf_counter()
        for w in range(max_turns):
            wave = [s for s in sessions if s["turns"] > w]
            batch = []
            for s in wave:
                prev = history.get(s["sid"])
                base = s["sys"] if prev is None else prev
                p = np.concatenate([base, s["user"][w]])
                rid = w * 1000 + int(s["sid"][1:])
                batch.append((s, rid, p))
                prompt_tokens += len(p)
                eng.submit(Request(
                    rid=rid, tokens=p, max_new_tokens=s["new"][w],
                    expert=s["expert"],
                    session_id=s["sid"] if sharing else None))
            done = {r.rid: r for r in eng.drain()}
            for s, rid, p in batch:
                r = done[rid]
                outs[rid] = r.output
                ttfts.append(r.first_token_s - r.arrival_s)
                # next turn re-sends conversation so far (prompt + output)
                history[s["sid"]] = np.concatenate(
                    [p, r.output]).astype(np.int32)
        wall = time.perf_counter() - t0

        hit = int(eng.stats.prefix_hit_tokens - hit0)
        cow = int(eng.pool.stats.cow_splits - cow0)
        digest = hashlib.sha256(
            b"".join(outs[i].tobytes()
                     for i in sorted(outs))).hexdigest()[:16]
        per_tok = eng.pool._per_block_bytes() / eng.block
        if sharing:
            # zero-leak invariant: dropping retained sessions + the index
            # must return the pool to empty — refcounting never strands a
            # block
            eng.release_shared()
            if eng.pool.stats.blocks_in_use != 0:
                raise AssertionError(
                    f"prefix sharing leaked "
                    f"{eng.pool.stats.blocks_in_use} blocks after release")
        return {"wall": wall, "ttft_p99": float(np.percentile(ttfts, 99)),
                "ttft_p50": float(np.percentile(ttfts, 50)),
                "digest": digest, "hit_tokens": hit, "cow_splits": cow,
                "prompt_tokens": prompt_tokens,
                "kv_bytes_deduped": hit * per_tok,
                "evictions": (eng.sessions.evictions if sharing else 0)}

    best, rows = {}, []
    for rep in range(repeats):
        for mode, sharing in (("unshared", False), ("shared", True)):
            run = replay(sharing)
            b = best.setdefault(mode, run)
            if run["digest"] != b["digest"]:
                raise AssertionError(
                    f"{mode} run diverged across repeats "
                    f"(digest {run['digest']} != {b['digest']})")
            b["wall"] = min(b["wall"], run["wall"])
            b["ttft_p99"] = min(b["ttft_p99"], run["ttft_p99"])
            b["ttft_p50"] = min(b["ttft_p50"], run["ttft_p50"])
    if best["shared"]["digest"] != best["unshared"]["digest"]:
        raise AssertionError(
            "prefix sharing changed the token streams (digest "
            f"{best['shared']['digest']} != {best['unshared']['digest']})")
    for mode in ("unshared", "shared"):
        b = best[mode]
        rows.append({"mode": mode, "wall_s": b["wall"],
                     "ttft_p50_s": b["ttft_p50"],
                     "ttft_p99_s": b["ttft_p99"],
                     "hit_tokens": b["hit_tokens"],
                     "cow_splits": b["cow_splits"],
                     "prompt_tokens": b["prompt_tokens"],
                     "kv_bytes_deduped": b["kv_bytes_deduped"],
                     "token_digest": b["digest"]})
        emit(f"sweep_tenancy_{mode}", b["wall"] * 1e6,
             f"ttft_p50_ms={b['ttft_p50']*1e3:.0f},"
             f"ttft_p99_ms={b['ttft_p99']*1e3:.0f},"
             f"hit_tokens={b['hit_tokens']},"
             f"cow_splits={b['cow_splits']},digest={b['digest']}")
    avoided = best["shared"]["hit_tokens"] / best["shared"]["prompt_tokens"]
    ratio = best["unshared"]["ttft_p99"] / best["shared"]["ttft_p99"]
    emit("sweep_tenancy_summary", 0.0,
         f"prefill_tokens_avoided={avoided:.2f},"
         f"ttft_p99_speedup={ratio:.2f}x,"
         f"kv_MB_deduped={best['shared']['kv_bytes_deduped']/1e6:.2f},"
         f"tokens_identical=1")

    # ---- frontend pass (ungated rows): quotas + streaming ---------------
    eng = mk_engine(sharing=True)
    eng.warmup()
    fe = StreamingFrontend(eng, quotas={
        "paid": TenantQuota(max_concurrent=n_slots),
        "free": TenantQuota(max_concurrent=1)})
    streams, rejected = [], 0
    fe_prompt = np.concatenate(
        [sys_prompts[0], np.asarray([3, 1, 4], np.int32)])
    for i in range(4):
        tenant = "paid" if i < 2 else "free"
        try:
            streams.append(fe.submit(fe_prompt, 4, tenant=tenant,
                                     session_id=f"fe{i}",
                                     priority=1 if tenant == "paid" else 0,
                                     slo_ttft_s=5.0))
        except QuotaExceeded:
            rejected += 1
    streamed = sum(len(st.drain()) for st in streams)
    fe.join(timeout=120)
    fe.close()
    eng.release_shared()
    rows.append({"mode": "frontend", "submitted": len(streams),
                 "rejected_quota": rejected, "streamed_tokens": streamed})
    emit("sweep_tenancy_frontend", 0.0,
         f"submitted={len(streams)},rejected_quota={rejected},"
         f"streamed_tokens={streamed}")

    metrics = {
        "tenancy:shared:ttft_p99_s": best["shared"]["ttft_p99"],
        "tenancy:prefill_tokens_avoided_frac": float(avoided),
        "tenancy:unshared_vs_shared_ttft_p99": float(ratio),
        "tenancy:tokens_identical": 1.0,
    }
    doc = {"schema": 1,
           "config": {"arch": "samba-coe-expert-7b(reduced)",
                      "n_sessions": n_sessions, "n_experts": n_exp,
                      "sys_prompt_tokens": SYS, "prompt_share": 0.7,
                      "max_turns": max_turns, "repeats": repeats,
                      "dtype": "float32", "tiny": tiny},
           "rows": rows,
           "kv_bytes_deduped": best["shared"]["kv_bytes_deduped"],
           "session_evictions": best["shared"]["evictions"],
           "metrics": _gated_metrics(metrics)}
    (_results_dir() / "bench_tenancy.json").write_text(
        json.dumps(doc, indent=1))


# ----------------------------------------------------------------------
def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--sweep-arrival", action="store_true",
                    help="run ONLY the offered-load serving sweep "
                         "(run-to-completion vs continuous batching)")
    ap.add_argument("--sweep-switching", action="store_true",
                    help="run ONLY the Fig-12 switching sweep (expert count "
                         "x store backend, async prefetch vs cold reload)")
    ap.add_argument("--sweep-node", action="store_true",
                    help="run ONLY the multi-socket node sweep (tokens/s + "
                         "latency vs socket-group shape on 8 emulated "
                         "sockets)")
    ap.add_argument("--sweep-prefill", action="store_true",
                    help="run ONLY the prefill sweep (packed AOT buckets vs "
                         "sequential recompiles; disaggregated vs colocated "
                         "node on 8 emulated sockets)")
    ap.add_argument("--sweep-tenancy", action="store_true",
                    help="run ONLY the tenancy sweep (copy-on-write prefix "
                         "sharing + session retention vs re-prefill "
                         "baseline; asserts identical token streams)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized sweep configs (fewer experts/requests/"
                         "repeats); used by the bench-smoke CI job")
    ap.add_argument("--backend", default="both",
                    choices=["xla", "fused", "both"],
                    help="decode backends for the --sweep-arrival fusion "
                         "axis (serving/backends.py); 'both' additionally "
                         "asserts the greedy token streams are identical "
                         "across backends")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record engine/cache/node spans while benching and "
                         "export a Chrome-trace / Perfetto JSON here "
                         "(default results/trace_bench.json when the flag "
                         "is given with no value)", nargs="?",
                    const="__default__")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.trace_out is not None:
        obs_trace.enable()
    if args.sweep_node or args.sweep_prefill:
        # before ANY sweep dispatches: a combined invocation (e.g.
        # --sweep-arrival --sweep-node) must not let the earlier sweep
        # initialize the backend with too few devices
        _ensure_host_devices(8)
    benches = {
        "table1": bench_table1_intensity,
        "fig10": bench_fig10_fusion_speedup,
        "fig11": bench_fig11_kernel_calls,
        "fig12": bench_fig12_tableV_coe_latency,
        "fig13": bench_fig13_footprint,
        "tableIV": bench_tableIV_decode_throughput,
        "fig1": bench_fig1_switching_measured,
        "sweep": bench_sweep_arrival,
        "sweep_switching": bench_sweep_switching,
        "sweep_node": bench_sweep_node,
        "sweep_prefill": bench_sweep_prefill,
        "sweep_tenancy": bench_sweep_tenancy,
    }
    print("name,us_per_call,derived")
    any_sweep = (args.sweep_arrival or args.sweep_switching
                 or args.sweep_node or args.sweep_prefill
                 or args.sweep_tenancy)
    try:
        if any_sweep:
            if args.sweep_arrival:
                bench_sweep_arrival(tiny=args.tiny, backend=args.backend)
            if args.sweep_switching:
                bench_sweep_switching(tiny=args.tiny)
            if args.sweep_node:
                bench_sweep_node(tiny=args.tiny)
            if args.sweep_prefill:
                bench_sweep_prefill(tiny=args.tiny)
            if args.sweep_tenancy:
                bench_sweep_tenancy(tiny=args.tiny)
        else:
            for name, fn in benches.items():
                if args.only:
                    if args.only != name:
                        continue
                elif name in ("sweep", "sweep_switching", "sweep_node",
                              "sweep_prefill", "sweep_tenancy"):
                    continue          # heavy: opt-in via --sweep-* flags
                fn()
    except BaseException:
        # postmortem for the CI artifact: the flight recorder saw every
        # admit/switch/evict right up to the failure
        from repro.obs import flightrec, get_registry
        out = flightrec.dump(_results_dir() / "flight_bench.json",
                             get_registry(), reason="bench_failure")
        print(f"bench failed — flight-recorder bundle -> {out}")
        raise
    if args.trace_out is not None:
        obs_trace.disable()
        out = (args.trace_out if args.trace_out != "__default__"
               else _results_dir() / "trace_bench.json")
        path = obs_trace.export(out)
        doc = json.loads(Path(path).read_text())
        problems = obs_trace.validate_chrome_trace(doc)
        print(f"trace: {len(doc['traceEvents'])} events -> {path}"
              + (f" ({len(problems)} schema problems)" if problems else ""))
    csv_path = _results_dir() / "benchmarks.csv"
    if any_sweep or args.only:
        # partial runs append (dedup by row name) instead of clobbering
        old = []
        if csv_path.exists():
            new_names = {r.split(",")[0] for r in ROWS}
            old = [l for l in csv_path.read_text().splitlines()
                   if l and l.split(",")[0] not in new_names]
        csv_path.write_text("\n".join(old + ROWS) + "\n")
    else:
        csv_path.write_text("\n".join(ROWS) + "\n")


if __name__ == "__main__":
    main()
