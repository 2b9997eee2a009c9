"""An architecture is files: ``bench/arch/<model_type>.py``, found by a
configuration's ``model_type``. The weights a seed draws are pinned, a new
architecture runs a cell with no file the benchmark had edited, and a type
with no module fails at set-up, naming the file it looked for."""
import hashlib
import time

import jax
import numpy as np
import pytest

from toy import BENCH, TOY_MODEL, make

from benchlib import cell as cellmod, spec, weights

# sha256 over the leaves' bytes in tree order, recorded before the weight
# tree moved from benchlib/weights.py into bench/arch/llama.py
BASE_SHA256 = ("567d402a99a36b25deada99a8d9febb2"
               "f90c5115a6632e9db6e2d7eea4ee247c")
EXPERTS_SHA256 = ("34d9aef28f1500199a8d010b133c1dc7"
                  "d20e615362b7d3b41ab2a4c32c48af89")

# Llama, with each CoE expert drawing its own attention out-projection
TOY_ALIAS = '''"""Llama, but each CoE expert draws its own attention
out-projection."""
from pathlib import Path

from benchlib import spec

_llama = spec.load_module(Path(__file__).with_name("llama.py"))
model_config, check_config, shapes = (_llama.model_config,
                                      _llama.check_config, _llama.shapes)
decode_flops, prefill_flops = _llama.decode_flops, _llama.prefill_flops
KERNELS, calls_per_round = _llama.KERNELS, _llama.calls_per_round
EXPERT_LEAF = ("layers", "attn", "wo")
'''


def _digest(leaves) -> str:
    h = hashlib.sha256()
    for x in leaves:
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


def test_a_seed_draws_the_same_weights():
    assert _digest(jax.tree.leaves(weights.make_base(TOY_MODEL, 5))) \
        == BASE_SHA256
    assert _digest([weights.make_expert_leaves(TOY_MODEL, 5, 3)]) \
        == EXPERTS_SHA256


def _files(bench):
    return {p.relative_to(bench): p.read_bytes() for p in bench.rglob("*")
            if p.is_file() and not {"__pycache__", "tests"} & set(
                p.relative_to(bench).parts)}


def test_an_architecture_is_added_as_files_alone(tmp_path):
    model = dict(TOY_MODEL, name="toy-alias", model_type="toy_alias")
    root = make(tmp_path, model=model, config="toy-alias")
    bench = root / "bench"
    (bench / "arch/toy_alias.py").write_text(TOY_ALIAS)
    cell = spec.workload("toy-open", root)
    res = cellmod.run_cell(cell, 2**32 + 7, 1.5, False, time.perf_counter(),
                           bench=bench, root=root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # the new module drew the experts: they differ in the attention's
    # out-projection, not in the FFN's
    (_, a), (_, b) = weights.build_experts(model, 2, 5, bench)[0]
    assert not np.array_equal(a["layers"]["attn"]["wo"],
                              b["layers"]["attn"]["wo"])
    assert np.array_equal(a["layers"]["mlp"]["wo"], b["layers"]["mlp"]["wo"])
    had = _files(BENCH)
    assert had and all((bench / rel).read_bytes() == data
                       for rel, data in had.items())


def test_a_model_type_with_no_module_fails_at_setup(tmp_path):
    root = make(tmp_path, model=dict(TOY_MODEL, model_type="no_such_arch"))
    cell = spec.workload("toy-open", root)
    with pytest.raises(FileNotFoundError,
                       match=r"bench/arch/no_such_arch\.py"):
        cellmod.run_cell(cell, 1, 1.0, False, time.perf_counter(),
                         bench=root / "bench", root=root)
