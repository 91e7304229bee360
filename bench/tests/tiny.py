"""A throwaway benchmark root for CPU tests: a copy of ``bench/`` with tiny
configurations of both architectures and a short mix, and a
``BENCHMARK.json`` naming them with every metric reader in ``bench/``."""

from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

SIZES = {
    "stablelm-12b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                         head_dim=16, d_ff=128, vocab_size=512,
                         norm_kind="layernorm", qkv_bias=False),
    "chatglm3-6b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=96, vocab_size=512,
                        norm_kind="rmsnorm", qkv_bias=True),
}

MIXES = {
    "tiny-offline": {
        "driver": "offline",
        "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                          "min": 4, "max": 24},
        "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                          "min": 2, "max": 16},
        "block": 16, "queue_blocks": 64, "why": "test"},
}


# The tiny configurations' limit on the widest logit gap, set between the
# program's readings on the CPU (at most 0.0343 over 6 seeds of each
# architecture and mode) and the float8 control's (at least 0.174).
TINY_GAP_LIMIT = 0.09


def config(arch: str, mode: str, limit: float = TINY_GAP_LIMIT, **block) -> dict:
    """A tiny configuration; ``block`` overrides its block fields."""
    c = dict(name=f"tiny-{arch}-{mode}", arch=arch, family="dense",
             reference="bench/reference.py", norm_eps=1e-6,
             rope_theta=10000.0, rope_fraction=0.5, parallel_block=False,
             gated_ffn=True, ffn_act="silu", dtype="bfloat16",
             bw=4, ba=4, mode=mode, batch=4, max_seq=64,
             logit_gap_limit=limit, **SIZES[arch])
    return dict(c, **block)


def make_root(tmp: pathlib.Path, cells: list[tuple[str, str, str]]) -> pathlib.Path:
    """A root holding ``bench/`` and a ``BENCHMARK.json`` of ``cells``, each
    ``(arch, mode, mix)``; the cell is named ``<arch>-<mode>-<mix>``."""
    root = tmp / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    configs, workloads = {}, []
    for arch, mode, mix in cells:
        c = config(arch, mode)
        path = f"bench/configs/{c['name']}.json"
        (root / path).write_text(json.dumps(c))
        configs[c["name"]] = dict(name=c["name"], source="test", file=path,
                                  reduced=[], why="test")
        (root / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(MIXES[mix]))
        workloads.append(dict(name=f"{arch}-{mode}-{mix}", config=c["name"],
                              traffic=mix, chips=1, why="test"))
    names = [w["name"] for w in workloads]
    metric = lambda name, unit, **kw: dict(name=name, unit=unit,
                                           better="lower", workloads=names, **kw)
    e2e = [metric(n, u, bound=0.05, source="host_clock") for n, u in (
        ("tokens_per_s", "tokens/s"), ("setup_s", "s"))]
    per_layer = [metric(p.stem, "x", source="device_trace", layer="any",
                        moves="tokens_per_s")
                 for p in sorted((REPO / "bench" / "metrics").glob("*.py"))
                 if p.stem not in {m["name"] for m in e2e}]
    bench = dict(real, configs=list(configs.values()), workloads=workloads,
                 end_to_end=e2e, per_layer=per_layer)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
