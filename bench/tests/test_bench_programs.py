"""Attribution of a trace to the program's spans and named scopes: idle
time by program span, spans counted, device seconds by program and scope,
and the three readings made from them, on hand-made events and on a trace
recorded on one TPU v5 lite chip (``data/``)."""
import gzip
import json
from pathlib import Path

import pytest

from benchlib import programs, rooflines, tracing

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
ENG, WORKER = "python/0", "python/1"


def _span(name, a, b, thread=ENG):
    return [name, round(a * MS), round((b - a) * MS), thread]


def _hand_made():
    ops = [  # label, start, duration; program and scope beside
        (["prefill", 2.0, 0.5], ["prefill_packed", ""]),
        (["copy.1", 5.5, 1.0], ["decode_step", ""]),
        (["qkv_rope_paged.3", 6.5, 1.0], ["decode_step", "qkv_rope"]),
        (["fusion.2", 7.5, 0.5], ["decode_step", "kv_scatter"]),
        (["argmax", 8.0, 0.5], ["sample", ""]),
        (["stray", 13.0, 1.0], ["(none)", ""])]
    host = [
        _span("engine.step", 0, 10), _span("engine.pick", 0, 1),
        _span("engine.admission", 1, 3), _span("engine.prefill", 1.5, 2.5),
        _span("engine.sync", 2.2, 2.5), _span("engine.prefetch_next", 3, 3.5),
        _span("engine.decode", 4, 9), _span("engine.decode_inputs", 4, 5),
        _span("kernel.decode_kernel", 5, 5.5), _span("engine.sample", 5.5, 6),
        _span("engine.sync", 6, 9), _span("engine.emit", 9, 9.5),
        _span("engine.step", 12, 16), _span("engine.pick", 12, 12.5),
        _span("engine.decode", 13, 15), _span("engine.sync", 13.5, 15),
        _span("switch.h2d", 10, 14, WORKER)]
    return {"devices": {"0": [[n, int(s * MS), int(d * MS)]
                              for (n, s, d), _ in ops]},
            "host": [["bench.traced", 0, 20 * MS]],
            "program_host": host,
            "op_meta": {"0": [m for _, m in ops]},
            "device_programs": {"0": [[p, round(a * MS), round((b - a) * MS)]
                                      for p, a, b in (
                                          ("prefill_packed", 1.8, 2.6),
                                          ("decode_step", 5.2, 8.0),
                                          ("sample", 8.0, 8.6))]}}


def test_hand_made_program_attribution():
    ev = _hand_made()
    pr = programs.reduce(ev)
    red = tracing.reduce(ev)
    # busy [2, 2.5] + [5.5, 8.5] + [13, 14]; idle [0, 2], [2.5, 5.5],
    # [8.5, 13], [14, 20], each split piece by piece over the spans
    assert red["busy_s"] == pytest.approx(0.0045)
    assert pr["idle_s"] == pytest.approx(0.0155)
    assert pr["program_gaps"] == pytest.approx({
        "engine.pick": 0.0015, "engine.admission": 0.001,
        "engine.prefill": 0.0005, "engine.prefetch_next": 0.0005,
        "engine.step": 0.0025, "engine.decode_inputs": 0.001,
        "kernel.decode_kernel": 0.0005, "engine.sync": 0.0015,
        "engine.emit": 0.0005, "host:none": 0.006})
    assert sum(pr["program_gaps"].values()) == pytest.approx(pr["idle_s"])
    assert pr["step_children"] == pytest.approx({
        "engine.pick": 0.0015, "engine.admission": 0.0015,
        "engine.prefetch_next": 0.0005, "engine.step": 0.0025,
        "engine.decode": 0.003, "engine.emit": 0.0005, "outside": 0.006})
    # the worker's copy overlaps the idle stretch [10, 13]
    assert pr["worker_overlap"] == pytest.approx({"switch.h2d": 0.003})
    # idle while a program ran: [1.8, 2] and [2.5, 2.6] in the prefill,
    # [5.2, 5.5] in the decode step, [8.5, 8.6] in the argmax
    assert pr["idle_by_program"] == pytest.approx({
        "prefill_packed": 0.0003, "decode_step": 0.0003, "sample": 0.0001,
        programs.BETWEEN: 0.0148})
    assert pr["span_program_gaps"]["kernel.decode_kernel|decode_step"] \
        == pytest.approx(0.0003)
    assert pr["span_program_gaps"]["engine.sync|sample"] == pytest.approx(
        0.0001)
    assert pr["span_program_gaps"][f"engine.sync|{programs.BETWEEN}"] \
        == pytest.approx(0.0014)
    assert pr["program_counts"]["engine.step"] == 2
    assert pr["program_counts"]["engine.sync"] == 3
    assert pr["scope_ops"] == pytest.approx({
        "prefill_packed": 0.0005, programs.CARRY: 0.001,
        "decode_step/qkv_rope": 0.001, "decode_step/kv_scatter": 0.0005,
        "sample": 0.0005, "(none)": 0.001})
    assert sum(pr["scope_ops"].values()) == pytest.approx(red["busy_s"])
    got = programs.summary(pr, red)
    assert got["decode_host_gap_ms"] == pytest.approx(3.0 / 2)
    assert got["sched_host_gap_ms"] == pytest.approx((2.5 + 1.5 + 1.5
                                                      + 0.5) / 2)
    assert got["decode_scan_copy_share"] == pytest.approx(100 * 1 / 4.5)


def test_nothing_to_read_without_program_spans():
    """A program that writes no spans or names (an older tree) gives no
    reduction and no readings, and raises nothing."""
    ev = _hand_made()
    ev["program_host"] = []
    assert programs.reduce(ev) is None
    assert programs.summary(None, tracing.reduce(ev)) == {
        "decode_host_gap_ms": None, "sched_host_gap_ms": None,
        "decode_scan_copy_share": None}
    ev = _hand_made()
    ev["op_meta"] = {"0": [["jit__lambda_", ""]] * 6}
    pr = programs.reduce(ev)
    assert programs.decode_scan_copy_share(pr, 0.0045) is None


def test_names_of_programs_and_scopes():
    assert programs.program_of("jit_decode_step(1234)") == "decode_step"
    assert programs.program_of("jit_sample") == "sample"
    assert programs.scope_of("jit(decode_step)/while/body/closed_call/"
                             "qkv_rope/qkv_rope_paged/pallas_call") \
        == "qkv_rope"
    assert programs.scope_of("jit(decode_step)/while/body/closed_call/"
                             "attention/jit(decode_paged)/transpose") \
        == "attention"
    assert programs.scope_of("jit(decode_step)/while/body/dynamic_slice") \
        is None
    runs = [(0, 10, "decode_step"), (20, 30, "sample")]
    assert [programs._module_at(runs, [0, 20], t)
            for t in (-1, 0, 9, 10, 25, 30)] == [
        "", "decode_step", "decode_step", "", "sample", ""]


def test_scopes_found_in_the_compiled_program_by_instruction():
    """A TPU trace's operation events carry the HLO text without its
    metadata; the compiled program's text gives each instruction's
    op_name."""
    hlo = (
        '%fusion.23 = bf16[1025,16,8,128]{3,2,1,0} fusion(%a, %b), '
        'kind=kLoop, metadata={op_name="jit(decode_step)/while/body/'
        'closed_call/kv_scatter/scatter" source_file="x.py"}\n'
        '  %copy.56 = bf16[2,1025,16,8,128]{4,3,2,1,0} copy(%c)\n'
        '  ROOT %qkv_rope_paged.3 = bf16[48,8,128]{2,1,0} custom-call(%d), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit('
        'decode_step)/while/body/closed_call/qkv_rope/qkv_rope_paged/'
        'pallas_call"}\n')
    known = programs.op_names(hlo)
    assert set(known) == {"fusion.23", "qkv_rope_paged.3"}
    scope = lambda text: programs.scope_of(programs._op_name(text, known))
    assert scope("%fusion.23 = bf16[1025,16,8,128]{3,2,1,0} fusion(...)") \
        == "kv_scatter"
    assert scope("%qkv_rope_paged.3 = bf16[48,8,128] custom-call(...)") \
        == "qkv_rope"
    assert scope("%copy.56 = bf16[2,1025,16,8,128] copy(...)") is None


def test_trim_keeps_attributions_beside_their_operations():
    ev = _hand_made()
    cut = programs.trim(ev, 5 * MS, 9 * MS)
    assert [x[0] for x in cut["devices"]["0"]] == [
        "copy.1", "qkv_rope_paged.3", "fusion.2", "argmax"]
    assert cut["op_meta"]["0"][1] == ["decode_step", "qkv_rope"]
    assert [x[0] for x in cut["device_programs"]["0"]] == ["decode_step",
                                                           "sample"]
    assert cut["host"][0] == ["bench.traced", 5 * MS, 4 * MS]
    assert all(x[1] < 9 * MS for x in cut["program_host"])


@pytest.mark.parametrize("path", sorted(DATA.glob("*.program.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_program_trace(path):
    """Hand counts of the recorded trace are kept beside it."""
    ev = json.loads(gzip.decompress(path.read_bytes()))
    want = json.loads(path.with_name(
        path.name.replace(".program.json.gz", ".program-counts.json"))
        .read_text())
    red = tracing.reduce(ev)
    pr = programs.reduce(ev)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert pr["idle_s"] == pytest.approx(want["window_s"] - want["busy_s"],
                                         rel=1e-9)
    assert pr["program_counts"] == want["program_counts"]
    for key in ("program_gaps", "step_children", "idle_by_program",
                "span_program_gaps", "scope_ops"):
        assert set(pr[key]) == set(want[key]), key
        for k, v in want[key].items():
            assert pr[key][k] == pytest.approx(v, rel=1e-6, abs=1e-9), k
    assert sum(pr["program_gaps"].values()) == pytest.approx(pr["idle_s"])
    assert sum(pr["scope_ops"].values()) == pytest.approx(red["busy_s"])
    for k, v in programs.summary(pr, red).items():
        assert v == pytest.approx(want["readings"][k], rel=1e-6), k
    # each fused kernel runs under its own scope of the decode step
    names = rooflines.names(red, dict(want["model"], model_type="llama"))
    for op, kernel in names.items():
        i = next(j for j, x in enumerate(ev["devices"]["0"]) if x[0] == op)
        assert ev["op_meta"]["0"][i] == ["decode_step",
                                         programs.KERNEL_SCOPES[kernel]]
