"""Traffic: seeds, warm-up coverage, and finding new files by name."""

import json
import types

import numpy as np
import pytest

import tiny
from bench import generate, run_cell
from conftest import REPO
from repro.serve.serving import Request, ServeEngine, bucket_to

MIXES = sorted(p.stem for p in (REPO / "bench" / "traffic").glob("*.json"))
BIG = 2**33 + 123


def mix(name: str) -> dict:
    return json.loads((REPO / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_the_same_seed_gives_the_same_requests(name):
    m = mix(name)
    a, b = (generate.requests(m, 100352, BIG, 200) for _ in range(2))
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    c = generate.requests(m, 100352, BIG + 1, 200)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # Another seed sends the same sizes, in another order, block by block.
    n = m["block"]
    for i in range(0, 192, n):
        sizes = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs[i:i + n])
        assert sizes(a) == sizes(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_prefill_bucket_of_a_mix_is_warmed_up(name):
    m = mix(name)
    warmed = set(generate.prefill_buckets(m, bucket_to, 8))
    engine = types.SimpleNamespace(prompt_bucket=8, max_seq=2048)
    for seed in range(5):
        reqs = generate.requests(m, 1000, seed, 256)
        for i in range(0, 256, 8):
            wave = [Request(prompt=r.prompt, max_new_tokens=r.max_new)
                    for r in reqs[i:i + 8]]
            for k in range(1, 9):
                assert ServeEngine._wave_bucket(engine, wave[:k]) in warmed
    d = m["prompt_tokens"]
    assert bucket_to(d["max"], 8) + m["output_tokens"]["max"] <= 2048


def test_new_configuration_mix_and_metric_are_found_by_name(tmp_path):
    """A configuration, a mix and a metric reader added as files, with their
    entries in BENCHMARK.json, run with no edit to a file already there."""
    root = tiny.make_root(tmp_path, [("stablelm-12b", "dequant", "tiny-offline")])
    c = tiny.config("chatglm3-6b", "dequant", name="tiny-new")
    (root / "bench" / "configs" / "tiny-new.json").write_text(json.dumps(c))
    long_prompts = dict(tiny.MIXES["tiny-offline"], prompt_tokens=dict(
        dist="uniform", min=20, max=32))
    (root / "bench" / "traffic" / "tiny-long.json").write_text(
        json.dumps(long_prompts))
    (root / "bench" / "metrics" / "shortest_prompt.py").write_text(
        "def read(run):\n"
        "    return min(r.prompt_len for r in run.log.reqs.values())\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-new", source="test",
                                 file="bench/configs/tiny-new.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name="new-cell", config="tiny-new",
                                   traffic="tiny-long", chips=1, why="test"))
    for m in bench["end_to_end"]:
        m["workloads"].append("new-cell")
    bench["end_to_end"].append(dict(
        name="shortest_prompt", unit="tokens", better="higher", bound=0.05,
        source="host_clock", workloads=["new-cell"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run_cell.Cell.load(root, "new-cell")
    res = run_cell.run(cell, BIG, 1.5, trace=False, device_check=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["shortest_prompt"]["value"] >= 20
    assert {"tokens_per_s", "setup_s"} <= set(res["metrics"])
