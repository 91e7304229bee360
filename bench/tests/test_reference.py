"""The plain reference against ``ServeEngine.generate``'s path, at tiny
sizes of both architectures in both servable modes, on the CPU: a whole run
(bar the look for a chip) must come out correct, and must not when one
projection of the reference is transposed."""

import pytest

import tiny
from bench import run_cell

ARCH_MODES = [(a, m) for a in tiny.SIZES for m in ("dequant", "pallas")]


def cell_of(tmp_path, arch, mode, mix="tiny-offline"):
    root = tiny.make_root(tmp_path, [(arch, mode, mix)])
    return run_cell.Cell.load(root, f"{arch}-{mode}-{mix}")


def run(cell, seconds=1.5):
    return run_cell.run(cell, 2**33 + 17, seconds, trace=False,
                        device_check=False)


@pytest.mark.parametrize("arch, mode", ARCH_MODES)
def test_served_tokens_agree_with_the_reference(tmp_path, arch, mode):
    res = run(cell_of(tmp_path, arch, mode))
    c = res["checks"]
    assert res["correct"], c
    assert c["tokens_compared"]["value"] >= 100
    assert c["malformed_requests"]["value"] == 0


@pytest.mark.parametrize("arch", list(tiny.SIZES))
def test_a_transposed_projection_fails(tmp_path, monkeypatch, arch):
    cell = cell_of(tmp_path, arch, "dequant")
    reference = cell.reference
    calls = []
    plain = reference.dequant

    def transposed_wo(codes, scale, bw):
        w = plain(codes, scale, bw)
        calls.append(None)
        # Projections are rebuilt in the order wq, wk, wv, wo, w_gate, w_up,
        # w_down; wo is square at these sizes.
        return w.T if len(calls) % 7 == 4 else w

    monkeypatch.setattr(reference, "dequant", transposed_wo)
    res = run(cell)
    assert calls, "the reference did not rebuild any projection"
    assert not res["correct"]
    assert (res["checks"]["widest_logit_gap"]["value"]
            > res["checks"]["widest_logit_gap"]["limit"])
