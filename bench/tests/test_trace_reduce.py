"""The trace reduction, on a hand-made trace and on a trace recorded on the
chip (committed trimmed)."""

import gzip
import json

import pytest

from bench import trace_reduce
from conftest import REPO

HOST, DEV = "/host:CPU", "/device:TPU:0"
M, O = trace_reduce.MODULES, trace_reduce.OPS


def test_hand_made_trace():
    evs = [
        (HOST, "python", trace_reduce.BEGIN, 1000, 1100),
        (HOST, "python", "bench.generate", 1200, 9000),
        (HOST, "python", "bench.on_wave", 4000, 4500),
        (HOST, "python", trace_reduce.END, 10000, 10050),
        (DEV, M, "jit_decode_wave(7)", 2000, 4000),
        (DEV, M, "jit_prefill_step(8)", 5000, 6000),
        (DEV, O, "fusion.0", 500, 1500),            # starts before the window
        (DEV, O, "%while.3 = (s32[]) while(...)", 2000, 4000),
        (DEV, O, "fusion.1", 2000, 3000),              # inside the while
        (DEV, O, "%lut_dequant_gemm.4 = f32[8,128]{1,0} custom-call(...)",
         3000, 3900),                                  # inside the while
        (DEV, O, "fusion.2", 5000, 6000),
        ("/device:TPU:0 SparseCore", O, "other", 2000, 9000),   # not a core
    ]
    r = trace_reduce.reduce_events(evs)
    assert r["window_s"] == pytest.approx(8900e-9)
    assert r["busy_s"] == pytest.approx(3400e-9)       # 400 + 2000 + 1000
    assert r["programs"] == pytest.approx({"decode_wave": 2000e-9,
                                           "prefill_step": 1000e-9})
    assert r["kernels"] == pytest.approx({"lut_dequant_gemm": 900e-9})
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.generate", "bench.on_wave",
                                    "bench.generate"]
    assert [g[1] for g in gaps] == pytest.approx([4000e-9, 1000e-9, 500e-9])
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.0"] == pytest.approx(400e-9)
    assert ops["while.3 (s32[]"] == pytest.approx(100e-9)   # self time


def test_a_trace_without_markers_is_refused():
    with pytest.raises(RuntimeError, match="markers"):
        trace_reduce.reduce_events([(DEV, O, "fusion.1", 0, 10)])


def test_recorded_trace_of_one_wave():
    """One offline wave of cell 1 on a v5e (the prefill of its admissions,
    the merge and a 167.8 ms decode wave), trimmed from a traced run: the
    programs' times are their module events', busy time is the union of the
    operations, and the top operations are the decode step's KV-cache
    copies."""
    path = REPO / "bench" / "tests" / "data" / "trace_v5e_wave.json.gz"
    with gzip.open(path, "rt") as f:
        evs = [tuple(e) for e in json.load(f)]
    r = trace_reduce.reduce_events(evs)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.211280935)
    assert r["programs"]["decode_wave"] == pytest.approx(0.167771978)
    assert r["programs"]["prefill_step"] == pytest.approx(0.028085121)
    assert r["programs"]["admit_merge"] == pytest.approx(0.00293371)
    # A program's event spans its operations and a few microseconds of
    # launch around them.
    assert sum(r["programs"].values()) == pytest.approx(r["busy_s"], rel=1e-4)
    assert r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(0.206748369)
    top = r["breakdown"]["device_ops"]
    assert len(top) == 10 and top[0][0].endswith("f32[8,2048,8,160]")
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["np.asarray(jax.Array)", pytest.approx(0.002087686)]
