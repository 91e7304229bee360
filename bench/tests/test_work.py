"""FLOP and least-byte counts against numbers worked by hand for one layer
of the benchmark's configuration and of chatglm3-6b's widths, and the peak
table."""

import json

import pytest

from bench import run_cell
from bench.work import dense
from conftest import REPO


def cfg(name: str, **kw) -> dict:
    if name == CHATGLM:
        # chatglm3-6b's published widths (arXiv:2406.12793), W4.
        return dict(d_model=4096, n_heads=32, n_kv_heads=2, head_dim=128,
                    d_ff=13696, vocab_size=65024, norm_kind="rmsnorm",
                    qkv_bias=True, bw=4, **kw)
    c = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    return dict(c, **kw)


STABLELM = "stablelm-12b-4l-w4a4-dequant"
CHATGLM = "chatglm3-6b-w4"


@pytest.mark.parametrize("name, mm, attn_per_key, layer_bytes, head_bytes", [
    # q, o: 5120x5120; k, v: 5120x1280; gate, up, down: 5120x13824.
    # sum K*F = 277,872,640; scales 45,568 channels x 2 B; two layer norms
    # of gain and shift, 2 x 2 x 5120 x 2 B; head 5120 x 100352 x 2 B plus
    # the final norm's 2 x 5120 x 2 B.
    (STABLELM, 555_745_280, 4 * 32 * 160, 138_936_320 + 91_136 + 40_960,
     1_027_604_480 + 20_480),
    # q, o: 4096x4096; k, v: 4096x256; gate, up, down: 4096x13696.
    # sum K*F = 203,948,032; scales 40,192 x 2 B; QKV bias 4,608 x 2 B; two
    # RMS norms 2 x 4096 x 2 B; head 4096 x 65024 x 2 B plus 4096 x 2 B.
    (CHATGLM, 407_896_064, 4 * 32 * 128, 101_974_016 + 80_384 + 9_216 + 16_384,
     532_676_608 + 8_192),
])
def test_one_layer_by_hand(name, mm, attn_per_key, layer_bytes, head_bytes):
    c = cfg(name, n_layers=1)
    v, d = c["vocab_size"], c["d_model"]
    assert dense.decode_flops(c, 100) == mm + attn_per_key * 100 + 2 * d * v
    # A 3-token prompt: 3 positions through the layer, attending 1 + 2 + 3
    # keys, and the head once.
    assert dense.prefill_flops(c, 3) == 3 * mm + attn_per_key * 6 + 2 * d * v
    assert dense.weight_bytes(c) == layer_bytes + head_bytes
    kv = 2 * c["n_kv_heads"] * c["head_dim"] * 2
    flops, nbytes = dense.decode_step(c, [10, 20])
    assert flops == dense.decode_flops(c, 10) + dense.decode_flops(c, 20)
    assert nbytes == (layer_bytes + head_bytes + 30 * kv
                      + 2 * (d * 2 + dense.act_bytes_per_token(c)))


def test_an_ungated_ffn_has_two_products():
    c = cfg(STABLELM, n_layers=1)
    gated = dense.decode_flops(c, 10)
    plain = dense.decode_flops(dict(c, gated_ffn=False), 10)
    assert gated - plain == 2 * 5120 * 13824
    assert dense.weight_bytes(c) - dense.weight_bytes(dict(c, gated_ffn=False)) \
        == 5120 * 13824 // 2 + 13824 * 2


def test_least_time():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert dense.least_time(2e12, 1e9, peak) == (2.0, "compute")
    assert dense.least_time(1e12, 3e9, peak) == (3.0, "memory")


def test_peaks_table_has_its_source_and_refuses_other_devices():
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = run_cell.peak_for(peaks, "TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"],
            v5e["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError, match="TPU v6 lite"):
        run_cell.peak_for(peaks, "TPU v6 lite")
