#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the root of the checkout,
and everything else by the names found there: the configuration file and
the plain reference it names, the traffic mix ``bench/traffic/<mix>.json``,
its driver ``bench/drivers/<driver>.py``, the work counts
``bench/work/<family>.py`` and one reader ``bench/metrics/<metric>.py`` per
metric.  The program serves the block that the configuration file states.

A run makes the weights from the seed, prepares them, warms up every
program shape its traffic can use (all of that is set-up), drives
``ServeEngine.generate`` through a window of ``--seconds``, and then checks
what the window served against the plain reference.  Untraced runs report
the cell's end-to-end metrics; ``--trace 1`` runs profile the start of the
window and report its per-layer metrics.  The last line of standard output
is one JSON object; the numbers compared for ``correct`` end standard error
and the JSON line.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero before printing a result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

# Requests checked against the reference: the longest finished one, then
# others drawn from the seed until this many served tokens are covered.
CHECK_TOKENS = 384
CHECK_MIN_REQUESTS = 3
CHECK_MAX_REQUESTS = 24


def load_module(path: pathlib.Path, name: str):
    """Import the Python file at ``path`` (its name may hold dots)."""
    if not path.is_file():
        raise SystemExit(f"no file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One cell of ``BENCHMARK.json`` and every file it names."""

    root: pathlib.Path
    cell: dict
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, root: pathlib.Path, workload: str) -> "Cell":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        cell = cells[workload]
        conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        cfg = json.loads((root / conf["file"]).read_text())
        mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
        mine = lambda m: workload in m.get("workloads", [workload])
        return cls(root, cell, cfg, mix,
                   [m for m in bench["end_to_end"] if mine(m)],
                   [m for m in bench["per_layer"] if mine(m)])

    def module(self, kind: str, name: str):
        return load_module(self.root / "bench" / kind / f"{name}.py", name)

    @functools.cached_property
    def reference(self):
        """The plain reference module that the configuration file names."""
        path = self.root / self.cfg["reference"]
        return load_module(path, path.stem)


def check_device(chips: int, peaks: dict):
    """The devices to run on; exits non-zero without a TPU or enough chips."""
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"this benchmark runs on a TPU; JAX found {d.platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees {len(devices)}")
    if d.device_kind not in peaks["devices"]:
        raise SystemExit(f"no peaks for device kind {d.device_kind!r} in "
                         f"bench/peaks.json")
    return devices


def peak_for(peaks: dict, device_kind: str) -> dict:
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return peaks["devices"][device_kind]


# Configuration keys that name the benchmark's own files, not the program's
# fields of the same name.
NOT_PROGRAM = {"name", "family"}
# The rotary fractions the program can serve, by its ``rope_kind``.
ROPE_KINDS = {0.0: "none", 0.5: "half", 1.0: "full"}


def program_config(cfg: dict, block: dict):
    """The program's model config: the architecture ``cfg["arch"]`` with
    every field that the configuration file states, and ``block`` (the
    reference's block fields, as the file states them or as the reference
    takes them where it states none)."""
    from repro.configs import get_config
    from repro.models.config import ModelConfig

    base = get_config(cfg["arch"])
    stated = {**cfg, **block}
    fields = {f.name for f in dataclasses.fields(ModelConfig)} - NOT_PROGRAM
    over = {}
    for k in fields & stated.keys():
        v, was = stated[k], getattr(base, k)
        if isinstance(v, dict) and dataclasses.is_dataclass(was):
            v = dataclasses.replace(was, **v)
        over[k] = tuple(v) if isinstance(v, list) else v
    frac = float(stated["rope_fraction"])
    if frac not in ROPE_KINDS:
        raise SystemExit(f"{cfg['name']} rotates {frac} of each head; the program "
                         f"rotates one of {sorted(ROPE_KINDS)}")
    over["rope_kind"] = ROPE_KINDS[frac]
    return dataclasses.replace(base, **over)


class Session:
    """What a driver needs: the engine, the traffic, and the window hooks."""

    def __init__(self, engine, cell: Cell, seed: int, seconds: float,
                 tracer, compiles):
        from repro.serve.serving import Request

        self.engine, self.mix, self.seed, self.seconds = engine, cell.mix, seed, seconds
        self.vocab, self.batch = cell.cfg["vocab_size"], cell.cfg["batch"]
        self.tracer, self.compiles = tracer, compiles
        self._request = Request
        self.t_window = None
        self.compiles_at_open = self.compiles_in_window = None

    def request(self, r):
        return self._request(prompt=r.prompt, max_new_tokens=r.max_new)

    def window_opened(self) -> None:
        self.t_window = time.perf_counter()
        self.compiles_at_open = self.compiles.snapshot()
        self.tracer.start()

    def wave_boundary(self, now: float) -> None:
        self.tracer.maybe_stop(now)

    def window_closed(self) -> None:
        self.tracer.stop()
        a, b = self.compiles_at_open, self.compiles.snapshot()
        self.compiles_in_window = (b[0] - a[0], b[1] - a[1])


class RunView:
    """What a metric reader sees of one finished run."""

    def __init__(self, cell: Cell, log, work, peak, batch, setup_s,
                 memory_peak_bytes, trace=None, tracer=None):
        self.cell, self.cfg, self.mix = cell.cell, cell.cfg, cell.mix
        self.log, self.work, self.peak, self.batch = log, work, peak, batch
        self.setup_s, self.memory_peak_bytes = setup_s, memory_peak_bytes
        self.trace, self.tracer = trace, tracer

    def window_waves(self) -> list:
        return self.log.window_waves()

    def traced_waves(self) -> list:
        """Waves whose programs ran inside the traced span."""
        if self.tracer is None or self.tracer.t_begin is None:
            return []
        return [w for w in self.log.waves
                if w.t_start >= self.tracer.t_begin
                and w.t_sync <= self.tracer.t_end]


def warm_up(engine, cell: Cell, seed: int) -> None:
    """Run every program shape the traffic can use once: each prefill bucket
    the mix's prompt lengths can produce (with the decode wave and the
    admission merge), and the host-side slice of each wave's used token
    columns, one per possible decode step count."""
    import jax.numpy as jnp
    import numpy as np

    from bench import generate
    from repro.serve.serving import Request, bucket_to

    cfg, mix = cell.cfg, cell.mix
    buckets = generate.prefill_buckets(mix, bucket_to, engine.prompt_bucket)
    worst = mix["output_tokens"]["max"]
    if buckets[-1] + worst > cfg["max_seq"]:
        raise SystemExit(f"bucket {buckets[-1]} + {worst} output tokens exceed "
                         f"max_seq {cfg['max_seq']}")
    rng = generate.rng_for(seed, 2)
    for b in buckets:
        prompt = rng.integers(0, cfg["vocab_size"], b, dtype=np.int32)
        engine.generate([Request(prompt=prompt, max_new_tokens=2)])
    stamp(f"warm-up of prefill buckets {buckets}")
    out = jnp.zeros((cfg["batch"], cfg["max_seq"]), jnp.int32)
    for n in range(1, worst + 1):
        np.asarray(out[:, :n])
    stamp(f"warm-up of {worst} token-column slices")


def stamp(what: str) -> None:
    """Print the set-up time so far, on standard error."""
    print(f"set-up: {time.perf_counter() - T_PROCESS!r} s after {what}",
          file=sys.stderr, flush=True)


def sample_checked(log, seed: int) -> list[int]:
    """Finished requests to check: the longest, then others drawn from the
    seed until ``CHECK_TOKENS`` served tokens are covered."""
    from bench import generate

    done = sorted(i for i, r in log.reqs.items() if r.done and r.tokens)
    if not done:
        return []
    longest = max(done, key=lambda i: (len(log.reqs[i].tokens),
                                       log.reqs[i].prompt_len))
    picked, total = [longest], len(log.reqs[longest].tokens)
    rest = [i for i in done if i != longest]
    order = generate.rng_for(seed, 3).permutation(len(rest))
    for j in order:
        if (total >= CHECK_TOKENS and len(picked) >= CHECK_MIN_REQUESTS) \
                or len(picked) >= CHECK_MAX_REQUESTS:
            break
        picked.append(rest[j])
        total += len(log.reqs[rest[j]].tokens)
    return picked


def malformed(log, vocab: int) -> int:
    """Requests the run finished with the wrong number of tokens or a token
    outside the vocabulary."""
    return sum(1 for r in log.reqs.values() if r.done and (
        len(r.tokens) != r.max_new or any(not 0 <= t < vocab for t in r.tokens)))


def check(cell: Cell, weights: dict, log, seed: int,
          control: bool = False) -> dict:
    """The numbers compared for ``correct``, each with its limit."""
    import numpy as np

    cfg, reference = cell.cfg, cell.reference
    picked = sample_checked(log, seed)
    gaps = [reference.served_gaps(weights, cfg, log.reqs[i].prompt, log.reqs[i].tokens,
                                  seq_len=cfg["max_seq"], control=control)
            for i in picked]
    flat = np.concatenate(gaps) if gaps else np.zeros((0,))
    return {
        "widest_logit_gap": {"value": float(flat.max()) if flat.size else None,
                             "limit": cfg["logit_gap_limit"]},
        "tokens_compared": {"value": int(flat.size), "limit": 1},
        "malformed_requests": {"value": malformed(log, cfg["vocab_size"]),
                               "limit": 0},
    }


def is_correct(checks: dict) -> bool:
    g, n, m = (checks["widest_logit_gap"], checks["tokens_compared"],
               checks["malformed_requests"])
    return (g["value"] is not None and g["value"] <= g["limit"]
            and n["value"] >= n["limit"] and m["value"] <= m["limit"])


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        device_check: bool = True, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.  With
    ``control`` it also holds the control's checks (``"control_checks"``,
    the reference in lower precision in the program's place)."""
    import jax

    from bench import weights as bench_weights
    from bench.tracing import CompileCounter, Tracer

    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if device_check:
        devices = check_device(cell.cell["chips"], peaks)
        peak = peak_for(peaks, devices[0].device_kind)
    else:
        devices = jax.devices()
        peak = next(iter(peaks["devices"].values()))
    compiles = CompileCounter()

    from repro.core import LutLinearSpec
    from repro.models.model import build_model
    from repro.serve.serving import ServeEngine

    cfg = cell.cfg
    model = build_model(program_config(cfg, cell.reference.block(cfg)))
    spec = LutLinearSpec(bw=cfg["bw"], ba=cfg["ba"], mode=cfg["mode"])
    abstract = jax.eval_shape(lambda k: model.quantize(model.init(k), spec),
                              jax.random.PRNGKey(0))
    stamp("imports and device check")
    w = bench_weights.make_weights(
        abstract, seed, bw=cfg["bw"], d_model=cfg["d_model"],
        rules=getattr(cell.reference, "LEAF_RULES", None))
    jax.block_until_ready(w)
    stamp("weights")
    params = model.prepare(bench_weights.program_tree(abstract, w))
    jax.block_until_ready(params)
    stamp("prepare")
    engine = ServeEngine(model, params, batch=cfg["batch"],
                         max_seq=cfg["max_seq"], decode="scan")
    warm_up(engine, cell, seed)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = Tracer(trace_dir)
    session = Session(engine, cell, seed, seconds, tracer, compiles)
    driver = cell.module("drivers", cell.mix["driver"])
    log = driver.run(session)
    setup_s = session.t_window - T_PROCESS
    memory_peak = max(int(d.memory_stats()["peak_bytes_in_use"])
                      for d in devices) if device_check else 0
    lowered, compiled = session.compiles_in_window
    print(f"compiles inside the window: {lowered} programs lowered, "
          f"{compiled} compiled", file=sys.stderr, flush=True)

    reduced = None
    if trace:
        from bench import trace_reduce

        reduced = trace_reduce.reduce(tracer.xplane())
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: {json.dumps(reduced['summary'])}", file=sys.stderr)
    view = RunView(cell, log, cell.module("work", cfg["family"]), peak,
                   cfg["batch"], setup_s, memory_peak, reduced, tracer)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.module("metrics", m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # The program's state goes before the reference runs.
    engine.params = None
    del engine, params, session
    gc.collect()
    checks = check(cell, w, log, seed)
    attempted = sum(1 for r in log.reqs.values()
                    if r.t_admit is not None or r.due is not None)
    failed = checks["malformed_requests"]["value"] + sum(
        1 for r in log.reqs.values() if r.due is not None and not r.done)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": is_correct(checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    if control:
        result["control_checks"] = check(cell, w, log, seed, control=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    cell = Cell.load(ROOT, args.workload)

    import jax

    # The compile cache lives in the checkout, at a fixed path (the path is
    # part of the cache key), and keeps every program, however small.
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
