"""Closed-loop ingest: one producer streams seeded points into
``Mixture.partial_fit`` on the runtime tier, one chunk per call, as fast
as the calls return.

Set-up makes the stream's chunks, as many as ``--seconds`` at the
traffic file's ``max_pts_per_s`` would take, builds the starting pool on
the device, hands it to the service through ``StreamRuntime.import_pool``
and ingests ``warmup_chunks`` chunks, which compiles every program the
window runs.  The window then ingests chunk after chunk and closes at the
end of the first chunk that returns ``--seconds`` after it opened (or at
the end of the last chunk made): ``ingest_pts_per_s`` is the points of
the window's chunks over the window's time, on the host's clock.

After the window the program's pool is compared with the reference's
replay of the same chunks (warm-up included) from the same starting
pool (``perfbench.compare``).
"""
from __future__ import annotations

import gc
import math
import time
from typing import List

import numpy as np

from perfbench import compare, deploy, figmn_ref, streams


def chunks(dep: deploy.Deployment, chunk: int, traffic,
           seconds: float) -> List[np.ndarray]:
    """The stream's chunks, enough for the warm-up and a window at
    ``max_pts_per_s``."""
    n = int(traffic["warmup_chunks"]) + int(math.ceil(
        seconds * float(traffic["max_pts_per_s"]) / chunk))
    return [streams.ingest_chunk(dep.model, dep.seed, chunk, i)
            for i in range(n)]


def run(s):
    from repro.api import Mixture, MixtureSpec
    from repro.stream import RuntimeConfig

    dep = deploy.deployment(s.config, s.seed)
    chunk = int(s.config["chunk"])
    warm = int(s.traffic["warmup_chunks"])
    feed = chunks(dep, chunk, s.traffic, s.seconds)
    mix = Mixture(MixtureSpec(model=dep.figmn_config(), tier="runtime",
                              runtime=RuntimeConfig(chunk=chunk)))
    mix.engine.import_pool(deploy.to_state(dep.start_pool()))
    for i in range(warm):
        mix.partial_fit(feed[i])
    s.setup_done()

    n = warm
    with s.window():
        t0 = time.perf_counter()
        while True:
            mix.partial_fit(feed[n])
            n += 1
            t1 = time.perf_counter()
            if t1 - t0 >= s.seconds or n == len(feed):
                break
    points = (n - warm) * chunk
    mem = s.memory_peak_bytes()
    state = mix.engine.state
    k_active = int(state.n_active)
    prog = deploy.from_state(state)
    del mix, state
    gc.collect()

    ref = dep.start_pool()
    for i in range(n):
        ref = figmn_ref.learn(ref, feed[i], dep.sigma, dep.threshold)
    gaps = compare.pool_gaps(prog, ref, dep.start_means(), n * chunk)
    ok, checks = compare.judge(gaps, s.limits)
    return {
        "end_to_end": {"ingest_pts_per_s": points / (t1 - t0)},
        "correct": ok, "checks": checks,
        "attempted": n - warm, "failed": 0,
        "memory_peak_bytes": mem,
        "context": {"dim": dep.dim, "k_active": k_active,
                    "points": points, "chunks": n - warm},
    }
