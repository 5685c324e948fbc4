#!/usr/bin/env python3
"""Readings for the correctness limits: a cell's compared numbers over
many seeds in one process, for the program or for the control.

    python3 perfbench/readings.py --workload <cell> --seeds 1,2,3 \
        --side program|control [--chunks 16] [--seconds 8]

Ingest cells: the program ingests ``--chunks`` chunks of each seed's
stream through ``Mixture.partial_fit`` from the starting pool, as the
window does, and is compared with the reference's replay; the control
(``perfbench/control.py``) replays the same chunks in bfloat16.  Read
cells: the program serves ``--seconds`` of the cell's traffic (its rate,
its snapshot, its micro-batcher, warm from the first seed on) and its
sampled answers are compared, as a run compares them; the control
answers as many requests in bfloat16.

One JSON line per seed: the gaps, the cell's limits and the numbers over
them; for ingest cells also the profile of the per-slot relative Λ gaps
(the largest few, the 90th percentile, counts over 1e-3, 1e-2, 1e-1).
The benchmark's runs never run this; PERF.md's limits come from it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from perfbench import (catalog, compare, control, deploy,  # noqa: E402
                       figmn_ref, streams)


def profile(prog, ref) -> dict:
    rel = np.asarray(compare.lam_rel(prog, ref))
    act = rel[np.asarray(ref["active"])]
    return {"top": np.sort(act)[::-1][:6].tolist(),
            "p90": float(np.percentile(act, 90)),
            "over": {t: int(np.sum(act > float(t)))
                     for t in ("1e-3", "1e-2", "1e-1")}}


def ingest_program(config, seed: int, chunks: int):
    from repro.api import Mixture, MixtureSpec
    from repro.stream import RuntimeConfig
    dep = deploy.deployment(config, seed)
    chunk = int(config["chunk"])
    xs = [streams.ingest_chunk(dep.model, seed, chunk, i)
          for i in range(chunks)]
    mix = Mixture(MixtureSpec(model=dep.figmn_config(), tier="runtime",
                              runtime=RuntimeConfig(chunk=chunk)))
    mix.engine.import_pool(deploy.to_state(dep.start_pool()))
    for x in xs:
        mix.partial_fit(x)
    prog = deploy.from_state(mix.engine.state)
    del mix
    gc.collect()
    ref = dep.start_pool()
    for x in xs:
        ref = figmn_ref.learn(ref, x, dep.sigma, dep.threshold)
    gaps = compare.pool_gaps(prog, ref, dep.start_means(), chunks * chunk)
    return gaps, profile(prog, ref)


def ingest_control(config, seed: int, chunks: int):
    dep = deploy.deployment(config, seed)
    low, ref = control.ingest_pools(config, seed, chunks)
    gaps = compare.pool_gaps(low, ref, dep.start_means(),
                             chunks * int(config["chunk"]))
    return gaps, profile(low, ref)


class ReadProgram:
    def __init__(self, config, traffic, seconds: float):
        self.config, self.traffic, self.seconds = config, traffic, seconds
        self.read = catalog.load_module("drivers", traffic["driver"])
        self.warm = True

    def __call__(self, seed: int, limits):
        read, t = self.read, self.traffic
        dep = deploy.deployment(self.config, seed)
        svc = read.Service(dep, t, int(self.config["chunk"]))
        due, kind, rows = read.schedule(dep, t, seed, read.rate_per_s(t),
                                        self.seconds)
        if self.warm:
            svc.warm(rows)
            self.warm = False
        w = svc.window(due, kind, rows, float(t["drain_s"]))
        svc.close()
        _, checks = read.check(dep, w, seed, t, limits)
        gaps = {k: v for k, (v, _) in checks.items()}
        gaps["unanswered"] = int(np.sum(~np.isfinite(w.lat)))
        return gaps, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--side", choices=("program", "control"),
                    required=True)
    ap.add_argument("--chunks", type=int, default=16)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    bench = catalog.benchmark(os.path.join(CHECKOUT, "BENCHMARK.json"))
    cell = catalog.cell(bench, args.workload)
    config = catalog.load_json("configs", cell["config"])
    traffic = catalog.load_json("traffic", cell["traffic"])
    limits = catalog.load_json("limits", args.workload)
    from perfbench.run import enable_compile_cache, require_chips
    require_chips(int(cell["chips"]))
    enable_compile_cache()
    ingest = traffic["driver"] == "ingest"
    if ingest:
        side = ingest_program if args.side == "program" else ingest_control
        reading = lambda seed: side(config, seed, args.chunks)  # noqa
    elif args.side == "program":
        prog = ReadProgram(config, traffic, args.seconds)
        reading = lambda seed: prog(seed, limits)  # noqa
    else:
        reading = lambda seed: (  # noqa
            control.read_gaps(config, traffic, seed), None)
    for seed in (int(s) for s in args.seeds.split(",")):
        gaps, prof = reading(seed)
        over = sorted(k for k, v in gaps.items()
                      if k in limits and not v <= limits[k])
        line = {"workload": args.workload, "side": args.side, "seed": seed,
                "gaps": gaps, "limits": limits, "over": over}
        if prof is not None:
            line["lam_rel_profile"] = prof
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
