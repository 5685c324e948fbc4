"""Benchmark harness — one section per paper table plus framework benches.

CSV convention: ``name,us_per_call,derived``.

  figmn_scaling   — the O(D³)→O(D²) complexity claim (scaling exponents)
  figmn_timing    — paper Tables 2–3 (train/infer time, both variants)
  figmn_accuracy  — paper Table 4 (quality parity, AUC/acc)
  figmn_runtime   — streaming-runtime points/sec across (D, K, chunk)
                    sweeps → BENCH_stream.json
  figmn_fleet     — multi-replica fleet: replicas × chunk throughput and
                    merged-vs-single-stream LL gap → BENCH_fleet.json
  figmn_autoscale — autoscaled vs fixed fleet under ramp load:
                    replicas-over-time, throughput, conservation-witnessed
                    scale events → BENCH_autoscale.json (CI-gated against
                    benchmarks/baselines/)
  figmn_sparse    — top-C shortlist vs dense hot paths: ingest points/sec
                    + serving scores/sec + held-out LL gap per (K, D, C)
                    → BENCH_sparse.json (CI-gated against
                    benchmarks/baselines/)
  figmn_predict   — conditional serving (eq. 27): dense vs shortlisted
                    predictions/sec + C=K bit-identity witness per
                    (K, D, o, C) → BENCH_predict.json (CI-gated against
                    benchmarks/baselines/)
  figmn_serve     — closed-loop serving: async request bursts ramp the
                    obs latency histogram's windowed p99 until the
                    autoscaler adds a replica off the serving signal
                    alone → BENCH_serve.json (CI-gated against
                    benchmarks/baselines/)
  figmn_faults    — fault-tolerance chaos run: seeded kill/hang/poison/
                    checkpoint-corruption mid-stream; gates detection
                    latency, recovery, exact mass accounting, serving
                    availability and held-out LL gap → BENCH_faults.json
                    (CI-gated against benchmarks/baselines/)
  figmn_multihost — worker-process fleet over repro.rpc: threaded-vs-
                    process equivalence, throughput scaling curve over
                    worker counts, exact mass conservation across RPC
                    scale events, SIGKILL-one-worker recovery with the
                    mass identity → BENCH_multihost.json (CI-gated
                    against benchmarks/baselines/)
  figmn_dispatch  — dispatch calibration: measured per-path cost table
                    + decision audit (table choice vs measured fastest
                    vs heuristic) → BENCH_dispatch.json +
                    BENCH_dispatch_table.json (CI-gated against
                    benchmarks/baselines/)
  lm_bench        — reduced-config LM substrate step times
  roofline        — §Roofline terms per (arch × shape) from the dry-run
                    artifacts (run repro.launch.dryrun --all first)

Run everything:  PYTHONPATH=src python -m benchmarks.run
Subset:          PYTHONPATH=src python -m benchmarks.run figmn_scaling ...
CI smoke:        PYTHONPATH=src python -m benchmarks.run --smoke
                 (every registered benchmark at a tiny size; any failure
                 exits non-zero so benchmark scripts cannot rot silently)
CI gates:        PYTHONPATH=src python -m benchmarks.run --check
                 (every CI-gated benchmark's fresh BENCH_*.json compared
                 against its committed benchmarks/baselines/ smoke
                 baseline; any regression exits non-zero)
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro import compile_cache

#: every registered benchmark module under benchmarks/; each exposes
#: ``main(smoke: bool = False)`` where smoke runs a tiny-size subset.
REGISTRY = ("figmn_scaling", "figmn_timing", "figmn_accuracy",
            "figmn_runtime", "figmn_fleet", "figmn_autoscale",
            "figmn_sparse", "figmn_predict", "figmn_serve",
            "figmn_faults", "figmn_multihost", "figmn_dispatch",
            "lm_bench", "roofline")

#: CI-gated benchmarks: module -> (fresh bench json, committed baseline);
#: each module exposes ``check(bench_path, baseline_path) -> bool``.
GATES = {
    "figmn_autoscale": ("BENCH_autoscale.json",
                        "benchmarks/baselines/BENCH_autoscale_smoke.json"),
    "figmn_sparse": ("BENCH_sparse.json",
                     "benchmarks/baselines/BENCH_sparse_smoke.json"),
    "figmn_predict": ("BENCH_predict.json",
                      "benchmarks/baselines/BENCH_predict_smoke.json"),
    "figmn_serve": ("BENCH_serve.json",
                    "benchmarks/baselines/BENCH_serve_smoke.json"),
    "figmn_faults": ("BENCH_faults.json",
                     "benchmarks/baselines/BENCH_faults_smoke.json"),
    "figmn_multihost": ("BENCH_multihost.json",
                        "benchmarks/baselines/"
                        "BENCH_multihost_smoke.json"),
    "figmn_dispatch": ("BENCH_dispatch.json",
                       "benchmarks/baselines/BENCH_dispatch_smoke.json"),
}


def _section(name: str, smoke: bool) -> bool:
    print(f"# --- {name} " + "-" * max(1, 60 - len(name)))
    t0 = time.time()
    try:
        importlib.import_module(f"benchmarks.{name}").main(smoke=smoke)
        print(f"# {name} done in {time.time() - t0:.1f}s")
        return True
    except Exception as e:                                 # keep harness alive
        print(f"# {name} FAILED: {type(e).__name__}: {e}")
        traceback.print_exc()
        return False


def _gate(name: str) -> bool:
    bench, baseline = GATES[name]
    print(f"# --- gate {name} " + "-" * max(1, 55 - len(name)))
    try:
        return bool(importlib.import_module(f"benchmarks.{name}")
                    .check(bench, baseline))
    except Exception as e:                                 # keep harness alive
        print(f"# gate {name} FAILED: {type(e).__name__}: {e}")
        traceback.print_exc()
        return False


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("names", nargs="*",
                    help=f"benchmarks to run (default: all of "
                         f"{', '.join(REGISTRY)})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for every benchmark; fail loudly")
    ap.add_argument("--check", action="store_true",
                    help="gate mode: compare each CI-gated benchmark's "
                         "fresh BENCH json against its committed smoke "
                         "baseline (no benchmarks are run)")
    args = ap.parse_args()
    unknown = set(args.names) - set(REGISTRY)
    if unknown:
        ap.error(f"unknown benchmarks: {', '.join(sorted(unknown))}")
    if args.check:
        want = args.names or list(GATES)
        not_gated = set(want) - set(GATES)
        if not_gated:
            ap.error(f"not CI-gated: {', '.join(sorted(not_gated))}")
        failed = [n for n in GATES if n in want and not _gate(n)]
        if failed:
            print(f"# FAILED gates: {', '.join(failed)}")
            sys.exit(1)
        return
    want = args.names or list(REGISTRY)
    failed = [n for n in REGISTRY if n in want
              and not _section(n, args.smoke)]
    if failed:
        print(f"# FAILED sections: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
