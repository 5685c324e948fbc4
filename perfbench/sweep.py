#!/usr/bin/env python3
"""Find the knee of an open-loop read cell by one sweep of rates.

    python3 perfbench/sweep.py --workload mnist-k1024.read --seed 1 \
        --seconds 10 --rates 200,400,800,1600

Builds the cell's service once, then offers each rate for ``--seconds``
and prints one JSON line per rate: the latency quantiles, how late the
client ran, and whether the queue kept up.  A rate keeps up when every
request was answered within a second of the window's end and the median
latency of the last fifth of the requests is under twice that of the
first fifth (a growing backlog raises it through the run).  The knee is
the highest rate that keeps up; the cell's traffic file offers 4/5 of
it.  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from perfbench import catalog, deploy  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = catalog.benchmark(os.path.join(CHECKOUT, "BENCHMARK.json"))
    cell = catalog.cell(bench, args.workload)
    config = catalog.load_json("configs", cell["config"])
    traffic = catalog.load_json("traffic", cell["traffic"])
    read = catalog.load_module("drivers", traffic["driver"])
    from perfbench.run import enable_compile_cache, require_chips
    require_chips(int(cell["chips"]))
    enable_compile_cache()
    dep = deploy.deployment(config, args.seed)
    svc = read.Service(dep, traffic, int(config["chunk"]))
    rates = [float(r) for r in args.rates.split(",")]
    _, _, rows = read.schedule(dep, traffic, args.seed, max(rates),
                               min(args.seconds, 1.0))
    svc.warm(rows)
    for rate in rates:
        due, kind, rows = read.schedule(dep, traffic, args.seed, rate,
                                        args.seconds)
        w = svc.window(due, kind, rows, float(traffic["drain_s"]))
        n = due.size
        fifth = max(n // 5, 1)
        head = float(np.median(w.lat[:fifth]))
        tail = float(np.median(w.lat[-fifth:]))
        ok = bool(np.isfinite(w.lat).all()
                  and w.seconds <= args.seconds + 1.0 and tail < 2 * head)
        print(json.dumps({
            "rate": rate, "requests": n,
            "answered": int(np.isfinite(w.lat).sum()),
            "p50_ms": 1e3 * read.percentile(w.lat, 50),
            "p95_ms": 1e3 * read.percentile(w.lat, 95),
            "p99_ms": 1e3 * read.percentile(w.lat, 99),
            "head_p50_ms": 1e3 * head, "tail_p50_ms": 1e3 * tail,
            "late_p50_ms": 1e3 * float(np.median(w.late)),
            "late_max_ms": 1e3 * float(w.late.max()),
            "window_s": w.seconds, "dispatches": w.dispatches,
            "rows_per_dispatch": w.coalesced_rows_sum / max(w.dispatches, 1),
            "keeps_up": ok}), flush=True)
    svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
