"""The control, at a size a test run can hold: the reference computed with
float8 operands, read on the same prompts and served tokens, must fail the
comparison that the program passes."""

import pytest

import tiny
from bench import run_cell


@pytest.mark.parametrize("arch", list(tiny.SIZES))
def test_the_control_fails_where_the_program_passes(tmp_path, arch):
    root = tiny.make_root(tmp_path, [(arch, "dequant", "tiny-offline")])
    cell = run_cell.Cell.load(root, f"{arch}-dequant-tiny-offline")
    res = run_cell.run(cell, 2**32 + 9, 1.5, trace=False, device_check=False,
                       control=True)
    program = res["checks"]["widest_logit_gap"]
    control = res["control_checks"]["widest_logit_gap"]
    assert res["correct"]
    assert not run_cell.is_correct(res["control_checks"])
    assert program["value"] <= program["limit"] < control["value"]
    assert control["value"] >= 3 * program["value"]
