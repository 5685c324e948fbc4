"""Open-loop reads: a Poisson stream of one-row requests, a mix of density
queries (``FleetCoordinator.score_async``) and label queries
(``predict_async`` of the label block given the features), against a
fleet's published snapshot, at the rate the traffic file fixes: ``load``
times the ``knee_per_s`` that a sweep on the chip found
(``perfbench/sweep.py``).

Set-up builds the snapshot on the device (``pool.served_pool``, shaped
by the traffic file's ``snapshot``), imports it into the one replica
(``StreamRuntime.import_pool``), publishes it and warms
up every batch the admission micro-batcher can form: for each kind and
each size up to its ``max_batch``, the concatenation of that many rows,
the read at that size, and the slicing of its answers.

The window: the client thread submits each request when it is due, by
the schedule of ``streams.poisson_schedule``; a collector takes each
answer to the host as it completes.  A request's latency runs from when
it was due to when its answer is on the host; one that is refused or
never completes counts as +inf.  After the close the collector waits up
to ``drain_s`` for the answers still out.

After the window a seeded sample of the answers of each kind is
compared with the reference's answers on the same snapshot.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import queue
import sys
import threading
import time

import numpy as np

from perfbench import compare, deploy, figmn_ref, streams

KINDS = ("score", "label")


def percentile(lat: np.ndarray, q: float) -> float:
    """Nearest-rank percentile; +inf entries are requests that failed."""
    s = np.sort(lat)
    return float(s[max(int(math.ceil(q / 100.0 * s.size)) - 1, 0)])


@dataclasses.dataclass
class Window:
    due: np.ndarray          # (n,) due offsets, s
    kind: np.ndarray         # (n,) index into KINDS
    rows: np.ndarray         # (n, dim)
    lat: np.ndarray          # (n,) s, +inf where no answer
    late: np.ndarray         # (n,) how late the client submitted, s
    answers: np.ndarray      # (n, width): each answer's values, NaN if none
    seconds: float           # open to last answer (or drain limit)
    drained: bool            # every accepted request answered
    dispatches: int
    coalesced_rows_sum: float


class Service:
    """The fleet tier with one published snapshot, warm for every batch
    the micro-batcher can form."""

    def __init__(self, dep: deploy.Deployment, traffic, chunk: int):
        from repro.api import Mixture, MixtureSpec
        from repro.fleet import FleetConfig
        from repro.fleet.scoring import AdmissionConfig
        from repro.obs import registry as obs_registry
        from repro.stream import RuntimeConfig

        self.dep, self.traffic = dep, traffic
        self.targets = tuple(range(dep.features, dep.dim))
        self.admission = AdmissionConfig(**traffic["admission"])
        self.mix = Mixture(MixtureSpec(
            model=dep.figmn_config(), tier="fleet",
            runtime=RuntimeConfig(chunk=chunk),
            fleet=FleetConfig(n_replicas=int(traffic["replicas"]),
                              admission=self.admission)))
        self.coord = self.mix.engine
        for replica in self.coord.replicas:
            replica.import_pool(deploy.to_state(
                dep.served_pool(traffic["snapshot"])))
        self.coord.consolidate()
        self._hist = obs_registry.default_registry().histogram(
            "figmn_serve_coalesced_rows")

    def warm(self, rows: np.ndarray) -> None:
        import jax.numpy as jnp
        f = self.dep.features
        for b in range(1, self.admission.max_batch + 1):
            for kind in KINDS:
                one = [jnp.asarray(rows[i:i + 1] if kind == "score"
                                   else rows[i:i + 1, :f])
                       for i in range(b)]
                xs = one[0] if b == 1 else jnp.concatenate(one, axis=0)
                out = (self.coord.score(xs) if kind == "score"
                       else self.coord.predict(xs, self.targets))
                for i in range(b):
                    np.asarray(out[i:i + 1])

    def window(self, due, kind, rows, drain_s: float) -> Window:
        n = due.size
        f = self.dep.features
        lat = np.full(n, np.inf)
        late = np.zeros(n)
        # answers go into one array: the client keeps no object per
        # request alive in the service's heap
        answers = np.full((n, max(1, self.dep.classes)), np.nan)
        done: "queue.SimpleQueue" = queue.SimpleQueue()
        t0 = time.perf_counter() + 0.01

        def collect():
            for _ in range(n):
                item = done.get()
                if item is None:
                    return
                i, fut = item
                if fut is None:            # refused at the door: +inf
                    continue
                try:
                    ans = np.asarray(fut.result()).ravel()
                except Exception:          # a failed request: +inf
                    continue
                lat[i] = time.perf_counter() - t0 - due[i]
                answers[i, :ans.size] = ans

        h0 = self._hist.snapshot()
        collector = threading.Thread(target=collect, daemon=True,
                                     name="perfbench-collect")
        collector.start()
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - t0 - due[i]
            try:
                if kind[i] == 0:
                    fut = self.coord.score_async(rows[i:i + 1])
                else:
                    fut = self.coord.predict_async(rows[i:i + 1, :f],
                                                   self.targets)
            except Exception:
                done.put((i, None))
                continue
            fut.add_done_callback(lambda fu, i=i: done.put((i, fu)))
        collector.join(timeout=drain_s)
        t_close = time.perf_counter()
        drained = not collector.is_alive()
        if not drained:
            done.put(None)
        h = self._hist.snapshot().delta(h0)
        return Window(due, kind, rows, lat, late, answers, t_close - t0,
                      drained, int(h.total), float(h.sum))

    def k_active(self) -> int:
        return int(self.coord.global_state.n_active)

    def close(self) -> None:
        self.mix.close()
        del self.mix, self.coord
        gc.collect()


def schedule(dep: deploy.Deployment, traffic, seed: int, rate: float,
             seconds: float):
    due, kind = streams.poisson_schedule(
        seed, rate, seconds, {k: traffic["kinds"][k] for k in KINDS})
    return due, kind, streams.draw(dep.model, seed, due.size, streams.READ)


def rate_per_s(traffic) -> float:
    return float(traffic["load"]) * float(traffic["knee_per_s"])


def check(dep: deploy.Deployment, w: Window, seed: int, traffic, limits):
    """Compare a seeded sample of each kind's answers with the
    reference's on the same snapshot."""
    per_kind = int(traffic["checked_per_kind"])
    rng = np.random.default_rng([seed % 2 ** 64, 7])
    ok = np.isfinite(w.lat)
    picks = []
    for k in range(len(KINDS)):
        idx = np.flatnonzero(ok & (w.kind == k))
        picks.append(np.sort(rng.choice(idx, min(per_kind, idx.size),
                                        replace=False)))
    ref = dep.served_pool(traffic["snapshot"])
    score_ref = np.asarray(figmn_ref.log_density(ref, w.rows[picks[0]]))
    label_ref = np.asarray(figmn_ref.conditional_mean(
        ref, w.rows[picks[1], :dep.features], n_out=dep.classes))
    score = w.answers[picks[0], 0]
    label = w.answers[picks[1], :dep.classes]
    gaps = compare.answer_gaps(score, score_ref, label, label_ref)
    return compare.judge(gaps, limits)


def run(s):
    t = s.traffic
    dep = deploy.deployment(s.config, s.seed)
    svc = Service(dep, t, int(s.config["chunk"]))
    due, kind, rows = schedule(dep, t, s.seed, rate_per_s(t), s.seconds)
    svc.warm(rows)
    s.setup_done()
    with s.window():
        w = svc.window(due, kind, rows, float(t["drain_s"]))
    mem = s.memory_peak_bytes()
    k_active = svc.k_active()
    svc.close()

    ok, checks = check(dep, w, s.seed, t, s.limits)
    answered = np.isfinite(w.lat)
    n = due.size
    stalls = w.due[w.late > 0.05]
    print(f"perfbench: {n} requests, {n - answered.sum()} failed, client "
          f"late p50 {np.median(w.late) * 1e3:.3f} ms max "
          f"{w.late.max() * 1e3:.3f} ms, {stalls.size} submissions over "
          f"50 ms late (at {np.round(stalls[:8], 3).tolist()} s), window "
          f"{w.seconds:.3f} s", file=sys.stderr, flush=True)
    io = (int((answered & (kind == 0)).sum()) * (dep.dim + 1)
          + int((answered & (kind == 1)).sum())
          * (dep.features + dep.classes))
    return {
        "end_to_end": {"read_p95_ms": 1e3 * percentile(w.lat, 95.0),
                       "read_p50_ms": 1e3 * percentile(w.lat, 50.0)},
        "correct": ok and w.drained, "checks": checks,
        "attempted": n, "failed": int(n - answered.sum()),
        "memory_peak_bytes": mem,
        "context": {"dim": dep.dim, "k_active": k_active,
                    "rows": int(answered.sum()), "io_values": io,
                    "dispatches": w.dispatches,
                    "coalesced_rows_sum": w.coalesced_rows_sum},
    }
