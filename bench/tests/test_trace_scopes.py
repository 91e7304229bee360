"""Device time by named scope and idle time by host span, on a hand-made
trace and on a trace of the scoped program recorded on the chip (committed
trimmed)."""

import gzip
import json

import pytest

from bench import trace_reduce, trace_scopes
from conftest import REPO

HOST, DEV = "/host:CPU", "/device:TPU:0"
M, O = trace_reduce.MODULES, trace_reduce.OPS
DW = "jit(decode_wave)/decode_loop/while/body"


def host(name, s, e):
    return (HOST, "python3", name, s, e)


def dev(line, name, s, e):
    return (DEV, line, name, s, e)


HAND_MADE = [
    host(trace_reduce.BEGIN, 1000, 1100),
    host("serve.wave", 1200, 6500),
    host("serve.admit", 1200, 1500),
    host("serve.prefill", 1500, 1900),
    host("serve.decode", 1900, 2000),
    host("serve.fetch", 2000, 4500),
    host("np.asarray(jax.Array)", 2100, 4400),     # not a serve span
    host("serve.emit", 4500, 6400),
    host("serve.wave", 6600, 9500),
    host("serve.admit", 6600, 7000),
    host("serve.wave", 9900, 12000),               # ends after the window
    host(trace_reduce.END, 10000, 10050),
    dev(M, "jit_decode_wave(7)", 2000, 4000),
    dev(M, "jit_prefill_step(8)", 5000, 6000),
    dev(O, "%while.1 = (s32[]) while(...)", 2000, 3900),
    dev(O, "%fusion.1 = bf16[8,64]{1,0} fusion(...)", 2000, 2500),
    dev(O, "%while.2 = (s32[]) while(...)", 2500, 3000),
    dev(O, "%copy.3 = f32[4,8]{1,0} copy(...)", 2600, 2900),   # no op_name
    dev(O, "%fusion.4 = f32[8,512]{1,0} fusion(...)", 3000, 3500),
    dev(O, "%fusion.5 = bf16[8,64]{1,0} fusion(...)", 3600, 3800),
    dev(O, "%convert.7 = bf16[16]{0} convert(...)", 3900, 4000),
    dev(O, "%fusion.6 = bf16[8,32,128]{2,1,0} fusion(...)", 5000, 6000),
]

# The decode_wave program's compiled HLO text, cut to the instructions the
# trace runs: the decode loop ``while.1`` holds the layer scan ``while.2``,
# whose body's carry copy ``copy.3`` has no op_name; ``convert.7`` runs
# outside any loop with none.
DECODE_WAVE_HLO = f"""HloModule jit_decode_wave, is_scheduled=true
%fused_computation.9 (p: f32[8]) -> f32[8] {{
  ROOT %n.9 = f32[8]{{0}} negate(f32[8]{{0}} %p), metadata={{op_name="{DW}/lm_head/neg"}}
}}
%body.22 (p.2: (s32[])) -> (s32[]) {{
  ROOT %copy.3 = f32[4,8]{{1,0}} copy(f32[4,8]{{1,0}} %x)
}}
%body.11 (p.1: (s32[])) -> (s32[]) {{
  %fusion.1 = bf16[8,64]{{1,0}} fusion(%a), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{DW}/layers/while/body/attention/qlinear/dot"}}
  %while.2 = (s32[]) while((s32[]) %t), condition=%cond.21, body=%body.22, metadata={{op_name="{DW}/layers/while"}}
  %fusion.4 = f32[8,512]{{1,0}} fusion(%b), kind=kOutput, metadata={{op_name="{DW}/lm_head/dot_general"}}
  ROOT %fusion.5 = bf16[8,64]{{1,0}} fusion(%c), kind=kLoop, metadata={{op_name="{DW}/add"}}
}}
ENTRY %main.1 (a.1: s32[]) -> (s32[]) {{
  %convert.7 = bf16[16]{{0}} convert(f32[16]{{0}} %d)
  ROOT %while.1 = (s32[]) while((s32[]) %a.1), condition=%cond.12, body=%body.11, metadata={{op_name="jit(decode_wave)/decode_loop/while"}}
}}"""

SCOPES_BY_PROGRAM = {
    "decode_wave": trace_scopes.op_scopes(DECODE_WAVE_HLO),
    "prefill_step": {"fusion.6 bf16[8,32,128]": "layers"},
}


def test_op_scopes_from_compiled_hlo_text():
    """Instructions are keyed as the trace's operations are, by name and
    result shape; one without a scope takes its caller's, through nested
    loops, and one outside any loop stays unscoped."""
    assert SCOPES_BY_PROGRAM["decode_wave"] == {
        "n.9 f32[8]": "lm_head",
        "copy.3 f32[4,8]": "layers",
        "fusion.1 bf16[8,64]": "qlinear",
        "while.2 (s32[]": "layers",
        "fusion.4 f32[8,512]": "lm_head",
        "fusion.5 bf16[8,64]": "decode_loop",
        "convert.7 bf16[16]": "unscoped",
        "while.1 (s32[]": "decode_loop",
    }


def test_hand_made_trace():
    r = trace_scopes.reduce_events(HAND_MADE, SCOPES_BY_PROGRAM)
    ns = lambda d: {k: round(v * 1e9, 6) for k, v in d.items()}
    assert ns(r["scopes"]["decode_wave"]) == {
        "qlinear": 500, "layers": 200 + 300, "lm_head": 500,
        "decode_loop": 1900 - 1700 + 200, "unscoped": 100}
    assert ns(r["scopes"]["prefill_step"]) == {"layers": 1000}
    assert ns(r["host_idle"]) == {
        "outside serve spans": 100 + 100 + 400, "serve.admit": 300 + 400,
        "serve.prefill": 400, "serve.decode": 100, "serve.fetch": 500,
        "serve.emit": 500 + 400, "serve.wave": 100 + 2500 + 100}
    assert r["waves"] == 2


def test_buckets_add_up_to_the_existing_reduction():
    """Each program's scopes sum to its operations' self time (here its
    module time), and the idle time by span to the window less busy time."""
    r = trace_scopes.reduce_events(HAND_MADE, SCOPES_BY_PROGRAM)
    base = trace_reduce.reduce_events(HAND_MADE)
    for prog, t in base["programs"].items():
        assert sum(r["scopes"][prog].values()) == pytest.approx(t)
    assert sum(r["host_idle"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])


@pytest.mark.parametrize("op_name,scope", [
    (f"{DW}/layers/while/body/attention/qlinear/dot_general", "qlinear"),
    ("jit(prefill_step)/layers/while/body/attention/kv_write/scatter", "kv_write"),
    (f"{DW}/layers/while/body/dynamic_update_slice", "layers"),
    (f"{DW}/dynamic_update_slice", "decode_loop"),
    ("jit(decode_wave)/while/body/dynamic_update_slice", None),
    ("jit(f)/sampler/reduce", None),
    ("", None),
])
def test_innermost_scope_of_an_op_name(op_name, scope):
    assert trace_scopes.scope_of(op_name) == scope


def test_a_trace_without_markers_is_refused():
    with pytest.raises(RuntimeError, match="markers"):
        trace_scopes.reduce_events([dev(O, "fusion.1", 0, 10)], {})


def test_recorded_trace_of_one_scoped_wave():
    """One offline wave of cell 1 on a v5e (a prefill at bucket 32, the
    merge and a 9-step decode wave), trimmed from a traced run of the
    scoped program, with each operation's scope from the compiled
    programs' HLO text.  The buckets add up; the whole-cache slices and
    updates of the stacked float32 KV cache land in ``layers`` and the
    decode loop's carry copies of it in ``decode_loop``; what no scope
    reaches is the per-wave work outside the decode loop."""
    path = REPO / "bench" / "tests" / "data" / "trace_v5e_scoped_wave.json.gz"
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    evs = [tuple(e) for e in data["events"]]
    r = trace_scopes.reduce_events(evs, data["op_scopes"])
    base = trace_reduce.reduce_events(evs)
    dw = r["scopes"]["decode_wave"]
    assert sum(dw.values()) == pytest.approx(base["programs"]["decode_wave"], rel=1e-5)
    assert sum(r["host_idle"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)
    assert r["waves"] == 1
    assert dw["layers"] == pytest.approx(0.058833177)
    assert dw["decode_loop"] == pytest.approx(0.028823522)
    assert dw["qlinear"] == pytest.approx(0.059014658)
    ops = lambda scope: [op for op, _t in r["scope_ops"][scope]]
    for op in ("bitcast_dynamic-update-slice_fusion.4 f32[4,8,2048,8,160]",
               "bitcast_dynamic-update-slice_fusion.5 f32[4,8,2048,8,160]",
               "dynamic-slice_bitcast_fusion.4 f32[8,2048,8,160]",
               "dynamic-slice_bitcast_fusion.5 f32[8,2048,8,160]"):
        assert op in ops("layers")
    assert ops("decode_loop")[:2] == ["copy.140 f32[4,8,2048,8,160]",
                                      "copy.139 f32[4,8,2048,8,160]"]
    # The LM head's weight conversion, hoisted out of the decode loop, is
    # the largest operation no scope reaches.
    assert ops("unscoped")[0] == "convert.825 bf16[100352,5120]"
    assert r["host_idle"]["serve.fetch"] == max(r["host_idle"].values())
