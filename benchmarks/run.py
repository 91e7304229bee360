# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

Sections:
  fig3   candidate LUT placements (§III-C)
  fig6   LUT capacity vs packing degree (§IV-B)
  fig9   GEMM speedups vs baselines (§VI-B)
  fig10  end-to-end DNN models (§VI-C)
  fig11  matrix-size sensitivity (§VI-D)
  fig12  packing-degree sensitivity (§VI-D)
  fig13  slice-count (k) sensitivity (§VI-D)
  fig16  GEMM kernel breakdown (§VI-G)
  fig18  cost-model validation (§VI-I)
  fig19  prefill/decode + batch scenarios (§VI-J)
  fig20  LUT-based bank-level PIM vs SIMD bank PIM (§VI-K)
  fig21  floating-point support via value-grid swap (§VI-K)
  functional  measured wall time of the exact LUT engines (CPU), incl. the
              tiled/deduplicated streamed engine vs the seed per-slice loop;
              also writes BENCH_stream.json at the repo root
  serve       weight-stationary serving: prepared params + scan decode vs the
              seed per-token loop, and continuous in-flight batching vs the
              fixed-chunk scheduler under a ragged Poisson-ish arrival mix
              (tokens/s, host-sync counts) at the fig13 default quant
              config; writes BENCH_serve.json at the repo root (now with an
              ``slo`` section from a repro.obs-traced run: TTFT/TPOT/queue
              percentiles + per-class goodput, and the zero-sync identity
              flags) plus BENCH_serve_trace.json (Perfetto) and
              BENCH_serve_metrics.jsonl
  tune        capacity-budgeted autotuned serving (repro.tune planner) vs a
              fixed whole-model LutLinearSpec, swept over >=3 LUT-budget
              points plus a degradation probe; verifies the plans' byte
              accounting against the prepared pytrees and writes
              BENCH_tune.json at the repo root
  roofline    TPU v5e roofline terms per (arch × shape) from the dry-run
              artifacts under runs/dryrun/.  Reading the artifacts needs no
              devices; *generating* them does — run the dry-run under forced
              host devices first:
                  XLA_FLAGS=--xla_force_host_platform_device_count=512 \
                      PYTHONPATH=src python -m repro.launch.dryrun --mesh single
"""

from __future__ import annotations

import json
import pathlib
import sys

from benchmarks import paper_figs, roofline
from benchmarks.common import emit

SECTIONS = {
    "fig3": paper_figs.fig3_candidates,
    "fig6": paper_figs.fig6_capacity,
    "fig9": paper_figs.fig9_gemm,
    "fig10": paper_figs.fig10_models,
    "fig11": paper_figs.fig11_size_sensitivity,
    "fig12": paper_figs.fig12_p_sensitivity,
    "fig13": paper_figs.fig13_k_sensitivity,
    "fig16": paper_figs.fig16_breakdown,
    "fig18": paper_figs.fig18_costmodel,
    "fig19": paper_figs.fig19_scenarios,
    "fig20": paper_figs.fig20_bank_level_pim,
    "fig21": paper_figs.fig21_float_support,
    "functional": paper_figs.functional_gemm_timing,
    "serve": paper_figs.serve_decode_benchmark,
    "tune": paper_figs.autotune_serve_benchmark,
    "roofline": roofline.rows,
}


_ROOT = pathlib.Path(__file__).resolve().parent.parent
STREAM_JSON = _ROOT / "BENCH_stream.json"
SERVE_JSON = _ROOT / "BENCH_serve.json"
SERVE_TRACE_JSON = _ROOT / "BENCH_serve_trace.json"
SERVE_METRICS_JSONL = _ROOT / "BENCH_serve_metrics.jsonl"
TUNE_JSON = _ROOT / "BENCH_tune.json"


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failed = []
    for name, fn in SECTIONS.items():
        if only and name != only:
            continue
        try:
            emit(fn())
        except Exception as e:
            # Keep running the other sections; the exit code reports this one.
            print(f"{name}/ERROR,,{type(e).__name__}:{e}")
            failed.append(name)
    # Persist the streamed-engine numbers so the perf trajectory is tracked
    # across PRs (written whenever the functional section ran).
    if paper_figs.LAST_STREAM_PAYLOAD is not None:
        STREAM_JSON.write_text(
            json.dumps(paper_figs.LAST_STREAM_PAYLOAD, indent=2) + "\n"
        )
        print(f"# wrote {STREAM_JSON}", file=sys.stderr)
    if paper_figs.LAST_SERVE_PAYLOAD is not None:
        SERVE_JSON.write_text(
            json.dumps(paper_figs.LAST_SERVE_PAYLOAD, indent=2) + "\n"
        )
        print(f"# wrote {SERVE_JSON}", file=sys.stderr)
    # The serve section's traced leg: archive the Perfetto trace + metrics
    # surface next to the payload (CI uploads both as build artifacts).
    if paper_figs.LAST_SERVE_TRACE is not None:
        SERVE_TRACE_JSON.write_text(
            json.dumps(paper_figs.LAST_SERVE_TRACE) + "\n"
        )
        print(f"# wrote {SERVE_TRACE_JSON}", file=sys.stderr)
    if paper_figs.LAST_SERVE_METRICS is not None:
        SERVE_METRICS_JSONL.write_text(
            "".join(json.dumps(r, separators=(",", ":")) + "\n"
                    for r in paper_figs.LAST_SERVE_METRICS)
        )
        print(f"# wrote {SERVE_METRICS_JSONL}", file=sys.stderr)
    if paper_figs.LAST_TUNE_PAYLOAD is not None:
        TUNE_JSON.write_text(
            json.dumps(paper_figs.LAST_TUNE_PAYLOAD, indent=2) + "\n"
        )
        print(f"# wrote {TUNE_JSON}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
