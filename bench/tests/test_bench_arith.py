"""Metric arithmetic on hand-made records: percentiles, the window's rate,
failed requests, the layer readers, the roofline functions and the peaks
table."""
import pytest

from toy import GRANITE, ROOT

from benchlib import endtoend, rooflines, spec
from benchlib.drive import Record

M = dict(GRANITE, model_type="llama")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def req(arrival, first, done, n_out, admit=None, paused=False):
    return {"rid": 0, "expert": "e", "prompt_len": 100, "max_new_tokens": n_out,
            "arrival": arrival, "submit": arrival,
            "admit": arrival if admit is None else admit, "first": first,
            "done": done, "n_out": n_out if done is not None else 0,
            "finished": done is not None, "paused": paused}


def record(reqs, tokens=100, window=10.0):
    return Record(window_s=window, t0=0.0, t_end=window, requests=reqs,
                  tokens_in_window=tokens,
                  counters={"serve.exec_s": 2.0, "serve.decode_rounds": 100,
                            "serve.occupancy_sum": 50.0,
                            "serve.prefill_s": 0.5,
                            "switch.switch_seconds": 1.5,
                            "switch.bytes_copied_in": 5.1e9,
                            "switch.h2d_seconds": 0.6})


def test_percentile_is_exact_and_linear():
    vals = list(range(1, 101))
    assert endtoend.pct(vals, 95) == pytest.approx(95.05)
    assert endtoend.pct([3.0], 95) == 3.0


def test_a_stall_in_the_window_moves_the_tail_and_the_rate():
    base = [req(i * 0.1, i * 0.1 + 0.05, i * 0.1 + 0.5, 10)
            for i in range(100)]
    calm = record(base, tokens=1000)
    # a 2 s stall at t=5: requests due in it wait for its end
    stalled = [dict(r, first=max(r["first"], 7.0) if 5.0 <= r["arrival"]
                    < 7.0 else r["first"]) for r in base]
    stall = record(stalled, tokens=800)
    ctx = {"drain_end": 20.0}
    tail = spec.metric_reader("ttft_tail_p95_s")
    assert tail(calm, ctx) == pytest.approx(0.05)
    assert tail(stall, ctx) > 1.0
    assert endtoend.tokens_per_s(calm, ctx) == 100.0
    assert endtoend.tokens_per_s(stall, ctx) == 80.0


def test_a_request_that_never_finishes_counts_until_the_drain_ends():
    reqs = [req(0.0, 0.1, 0.5, 5) for _ in range(19)] + [
        req(1.0, None, None, 5, admit=None)]
    rec = record(reqs)
    ttfts = sorted(endtoend.ttft(r, 61.0) for r in rec.requests)
    assert ttfts[-1] == 60.0
    assert sum(not r["finished"] for r in rec.requests) == 1
    # TPOT is over finished requests only: (0.5 - 0.1) / 4
    assert endtoend.tpot_p50_ms(rec, {}) == pytest.approx(100.0)
    assert spec.metric_reader("tpot_tail_p95_ms")(rec, {}) == \
        pytest.approx(100.0)


def test_setup_and_memory():
    rec = record([])
    rec.t0 = 42.5
    assert endtoend.setup_s(rec, {"start": 2.5}) == 40.0
    assert endtoend.peak_hbm_gb(rec, {"peak_bytes": 12_345_000_000}) == \
        pytest.approx(12.345)


def test_counter_readers():
    reqs = [req(0.0, 0.4, 1.0, 8, admit=0.3), req(1.0, 1.2, 2.0, 8,
                                                 admit=1.1),
            req(2.0, 9.0, 9.5, 8, admit=8.0, paused=True)]
    rec = record(reqs)
    ctx = {"m": M, "peaks": PEAKS, "trace": None}
    read = lambda n: spec.metric_reader(n)(rec, ctx)
    assert read("decode_step_ms") == pytest.approx(20.0)
    assert read("decode_occupancy") == pytest.approx(50.0)
    assert read("switch_stall_ms_per_req") == pytest.approx(500.0)
    assert read("h2d_gbps") == pytest.approx(8.5)
    # 300 prompt tokens admitted in the window
    assert read("prefill_ms_per_ktok") == pytest.approx(500 / 0.3)
    # the paused request is left out: waits 0.3 and 0.1
    assert read("queue_wait_p95_s") == pytest.approx(0.29)
    # trace readers find nothing to read without a trace
    for n in ("idle_share", "step_mfu", "decode_paged_roofline"):
        assert read(n) is None


def test_trace_readers():
    rec = record([])
    rec.rounds = [{"t": 0.0, "live": [1000, 2000], "n_slots": 32}]
    rec.prefills = [{"t": 0.0, "lengths": [768]}]
    attn = ("decode_paged.3 tpu_custom_call bf16[32,4,8,128] (s32[32,128],"
            "s32[32],bf16[32,4,8,128],bf16[4097,16,8,128],"
            "bf16[4097,16,8,128])")
    epi = ("closed_call.11 tpu_custom_call bf16[32,4096] (bf16[32,4096],"
           "bf16[32,4096],bf16[4096,4096],bf16[1,4096],bf16[4096,14336],"
           "bf16[4096,14336],bf16[14336,4096])")
    qkv = ("closed_call.10 tpu_custom_call bf16[48,32,128] (s32[32,1],"
           "bf16[32,4096],bf16[1,4096],f32[1,128],f32[1,128],"
           "bf16[4096,4096],bf16[4096,1024],bf16[4096,1024])")
    red = {"window_s": 0.01, "busy_s": 0.004, "n_gaps": 1, "gaps": {},
           "ops": {attn: 1e-3, epi: 2e-3, qkv: 1e-4,
                   "copy.5 copy bf16[4,8]": 1e-3}}
    kernels = rooflines.names(red, M)
    assert kernels == {attn: "decode_paged", epi: "oproj_ffn_swiglu",
                       qkv: "qkv_rope_paged"}
    ctx = {"m": M, "peaks": PEAKS, "trace": red, "kernels": kernels}
    read = lambda n: spec.metric_reader(n)(rec, ctx)
    assert read("idle_share") == pytest.approx(60.0)
    # hand count: K/V of 3000 live positions x 8 kv heads x 128 x 2 x 2
    kv = 3000 * 8 * 128 * 4 + 2 * 32 * 128 * 4 + 4 * (63 + 125)
    assert read("decode_paged_roofline") == pytest.approx(
        100 * 4 * (kv / 819e9) / 1e-3)
    w = 4096 * 4096 + 3 * 4096 * 14336
    ffn = 2 * w + 2 * 4096 + 2 * 2 * (3 * 4096)
    assert rooflines.share(rec, ctx, "oproj_ffn_swiglu") == pytest.approx(
        100 * 4 * (ffn / 819e9) / 2e-3)
    wq = 4096 * 48 * 128
    qkv_b = 2 * wq + 2 * 4096 + 2 * 2 * (4096 + 48 * 128)
    assert rooflines.share(rec, ctx, "qkv_rope_paged") == pytest.approx(
        100 * 4 * (qkv_b / 819e9) / 1e-4)
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    dense = 2 * (4 * per_layer + 4096 * 49152)
    decode = 2 * dense + 4 * 4 * 4096 * 3000
    prefill = (2 * 768 * 4 * per_layer + 2 * 4096 * 49152
               + 4 * 4 * 4096 * 768 * 769 / 2)
    assert read("step_mfu") == pytest.approx(
        100 * (decode + prefill) / (0.01 * 197e12))


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_every_metric_has_a_reader():
    b = spec.benchmark(ROOT)
    for x in b["per_layer"]:
        assert callable(spec.metric_reader(x["name"]))
    for x in b["end_to_end"]:
        assert x["name"] in endtoend.METRICS
