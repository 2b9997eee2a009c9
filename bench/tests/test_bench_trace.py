"""Trace reduction: busy and idle time, own time per device operation, the
labels of operations and the kernels they run, and the attribution of idle
gaps, on hand-made events and on a trace recorded on one TPU v5 lite chip
(granite-8b-code widths at 4 layers, 8 experts, open loop; trimmed to 120
ms, ``data/``)."""
import gzip
import json
from pathlib import Path

import pytest

from benchlib import rooflines, tracing

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def test_hand_made_trace():
    ev = {"devices": {"0": [
        ["while", 1 * MS, 3 * MS],
        ["fusion.1", 1 * MS, 2 * MS],                      # inside while
        ["fusion.2", 2 * MS, 1 * MS],                      # inside fusion.1
        ["copy.3", 7 * MS, 1 * MS],
        ["fusion.4", 11 * MS, 4 * MS]]},                   # runs past the end
        "host": [["bench.traced", 0, 12 * MS],
                 ["bench.step", 0, 6 * MS],
                 ["bench.extend", 0, 5 * MS],
                 ["bench.activate", 8 * MS, 3 * MS]]}
    red = tracing.reduce(ev)
    assert red["window_s"] == pytest.approx(0.012)
    # busy: [1, 4] + [7, 8] + [11, 12] = 5 ms
    assert red["busy_s"] == pytest.approx(0.005)
    # own time: while 3 - 2, fusion.1 2 - 1, fusion.2 1
    assert red["ops"] == pytest.approx({"while": 0.001, "fusion.1": 0.001,
                                        "fusion.2": 0.001, "copy.3": 0.001,
                                        "fusion.4": 0.001})
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"])
    assert red["calls"]["fusion.1"] == 1
    assert tracing.kernel_seconds(red, lambda k: k.startswith("fusion.")) \
        == pytest.approx(0.003)
    # gaps: [0,1] in extend, [4,7] mid 5.5 in step, [8,11] in activate
    assert red["gaps"] == pytest.approx({"bench.extend": 0.001,
                                         "bench.step": 0.003,
                                         "bench.activate": 0.003})
    assert red["n_gaps"] == 3
    bd = tracing.breakdown(red, {"fusion.2": "my_kernel"})
    assert bd["device_ops"][0][1] == pytest.approx(0.001)
    assert "my_kernel: fusion.2" in [k for k, _ in bd["device_ops"]]
    assert len(bd["idle_gaps"]) == 3


def test_no_window_no_reduction():
    assert tracing.reduce({"devices": {"0": []}, "host": []}) is None


def test_labels_of_hlo_text():
    kern = ('%decode_paged.3 = bf16[32,4,8,128]{3,2,1,0:T(8,128)(2,1)} '
            'custom-call(s32[32,128]{1,0:T(8,128)} %g.274, bf16[4097,16,8,'
            '128]{3,2,1,0:T(8,128)(2,1)S(1)} %f.20, bf16[4097,16,8,128]{3,2,'
            '1,0} %f.21), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={s32[32,128]{1,0}}')
    lab = tracing.label(kern)
    assert lab == ("decode_paged.3 tpu_custom_call bf16[32,4,8,128] "
                   "(s32[32,128],bf16[4097,16,8,128],bf16[4097,16,8,128])")
    op = rooflines.parse(lab)
    assert op.operands[1] == "bf16[4097,16,8,128]"
    assert rooflines.shape(op.result) == ("bf16", (32, 4, 8, 128))
    assert tracing.label("%while = (s32[], bf16[2]{0}) while((s32[], "
                         "bf16[2]{0}) %t), condition=%c") == "while while (tuple)"
    assert tracing.label("%copy.5 = bf16[4,8]{1,0:T(8,128)} copy(bf16[4,8]"
                         "{1,0} %x)") == "copy.5 copy bf16[4,8]"
    assert tracing.label("not hlo") == "not hlo"


@pytest.mark.parametrize("path", sorted(DATA.glob("*.events.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    """Hand counts of the recorded trace are kept beside it; the kernels
    are named by the shapes they take, from the recorded model's widths."""
    ev = json.loads(gzip.decompress(path.read_bytes()))
    want = json.loads(path.with_name(
        path.name.replace(".events.json.gz", ".counts.json")).read_text())
    m = dict(want["model"], model_type="llama")
    red = tracing.reduce(ev)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-9)
    assert red["n_gaps"] == want["n_gaps"]
    names = rooflines.names(red, m)
    assert set(names.values()) == set(want["kernel_s"])
    for k, v in want["kernel_s"].items():
        got = tracing.kernel_seconds(red, lambda x: names.get(x) == k)
        assert got == pytest.approx(v, rel=1e-9)
    for k, v in want["gaps_s"].items():
        assert red["gaps"].get(k, 0.0) == pytest.approx(v, rel=1e-6, abs=1e-9)
    bd = tracing.breakdown(red, names)
    assert any(k.startswith("decode_paged: ") for k, _ in bd["device_ops"])
