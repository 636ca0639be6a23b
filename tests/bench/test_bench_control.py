"""The control of ``correct``: the reference itself, computed with fp8
operands, put in the program's place.  At a toy size on the CPU the
harness's own verdict passes the bf16 program's served tokens and fails
the fp8 control's first choices, on the same requests."""
import time

import pytest

from benchroot import make_tiny_root
from harness.main import run_cell

LIMIT = 0.003   # toy-size readings, seeds 1-8: program <= 0.00044, control >= 0.0060


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"), gap_limit=LIMIT)


def run(root, seed, control):
    return run_cell(root, "tiny.chat", seed, 1.0, False, t_start=time.perf_counter(),
                    require_tpu=False, control=control, log=lambda *a, **k: None)


@pytest.mark.parametrize("seed", [1, 5])
def test_fp8_control_fails_where_the_program_passes(tiny_root, seed):
    r = run(tiny_root, seed, control=True)
    c = r["checks"]
    assert r["correct"] is False
    assert c["program_gap"]["value"] <= LIMIT < c["widest_gap"]["value"]
    assert list(r)[-1] == "checks"


def test_program_run_passes_the_same_verdict(tiny_root):
    r = run(tiny_root, 1, control=False)
    assert r["correct"] is True
    assert "program_gap" not in r["checks"]
