#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks
for.  The cell names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``); the mix names the driver that
generates it (``drivers/<name>.py``).  The driver builds the inputs from
the seed, warms up every shape it will use, measures for ``--seconds``
and checks what the timed path produced against the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler with the program's
spans on, and the result carries the cell's per-layer metrics, each read
by ``metrics/<name>.py`` from the trace and the program's counters.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: each number compared with its limit.  The same
checks are the last lines of standard error.  Without an accelerator, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench import catalog  # noqa: E402

#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so the second run of a cell finds every program the first compiled
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def enable_compile_cache(path: str = CACHE_DIR) -> None:
    import jax
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devs)}")
    return devs


class Session:
    """What a driver gets: the cell's files, the seed and the window's
    length, and the harness's clocks, counters and trace."""

    def __init__(self, cell, config, traffic, seed: int, seconds: float,
                 trace: bool, limits: Dict[str, float]):
        import jax
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = int(seed), seconds, trace
        self.limits = limits
        self.setup_s: Optional[float] = None
        self.compiles = 0
        self.window_compiles = 0
        self.reduced: Optional[Dict[str, Any]] = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1

    def setup_done(self) -> None:
        """Called by the driver when every shape is warm: set-up ends,
        with a full collection of set-up's garbage, so that every window
        starts from the same heap.  The window's own collections run as
        the service's would."""
        gc.collect()
        self.setup_s = time.monotonic() - _T_PROCESS

    @contextlib.contextmanager
    def window(self):
        """The measured window.  Counts the compiles inside it; on a traced
        run, records it under the profiler with the program's spans on."""
        import jax
        tmp = None
        if self.trace:
            from repro.obs import trace as obs_trace
            tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            obs_trace.enable(capacity=1 << 20, xla=True)
            jax.profiler.start_trace(tmp, profiler_options=opts)
        c0 = self.compiles
        try:
            with jax.profiler.TraceAnnotation("perfbench.window"):
                yield
        finally:
            self.window_compiles = self.compiles - c0
            print(f"perfbench: window compiles {self.window_compiles}",
                  file=sys.stderr)
            if self.trace:
                jax.profiler.stop_trace()
                obs_trace.disable()
        if tmp is not None:
            try:
                self.reduced = _reduce(tmp)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)

    @staticmethod
    def memory_peak_bytes() -> int:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))


def _reduce(trace_dir: str) -> Dict[str, Any]:
    from perfbench import tracing
    tr = tracing.load(trace_dir)
    win = tracing.window(tr)
    return {"trace": tr, "win": win,
            "window_s": (win[1] - win[0]) * 1e-9,
            "busy_s": tracing.busy_ns(tr, win) * 1e-9,
            "programs": tracing.programs(tr, win),
            "gaps": tracing.idle_gaps(tr, win)}


def _breakdown(red: Dict[str, Any]) -> Dict[str, Any]:
    progs = sorted(red["programs"].items(), key=lambda kv: -kv[1][0])
    return {"device_ops": [[n, s] for n, (s, _) in progs[:10]],
            "idle_gaps": [[n, s] for n, s, _ in red["gaps"][:10]]}


def _limits(name: str, root: str) -> Dict[str, float]:
    try:
        return catalog.load_json("limits", name, root)
    except catalog.UnknownName:
        raise catalog.UnknownName(
            f"no limits for cell {name!r}: add limits/{name}.json") from None


def run_cell(bench: Dict[str, Any], name: str, seed: int, seconds: float,
             trace: bool, root: str = catalog.ROOT,
             check_chips: bool = True) -> Dict[str, Any]:
    """One run of one cell; returns the result object."""
    cell = catalog.cell(bench, name)
    config = catalog.load_json("configs", cell["config"], root)
    traffic = catalog.load_json("traffic", cell["traffic"], root)
    driver = catalog.load_module("drivers", traffic["driver"], root)
    limits = _limits(name, root)
    group = "per_layer" if trace else "end_to_end"
    wanted = catalog.metrics_for(bench, name, group)
    readers = ({m["name"]: catalog.load_module("metrics", m["name"], root)
                for m in wanted} if trace else {})

    import jax
    if check_chips:
        devs = require_chips(int(cell["chips"]))
        enable_compile_cache()
    else:
        devs = jax.devices()
    session = Session(cell, config, traffic, seed, seconds, trace, limits)
    try:
        out = driver.run(session)
    finally:
        session.close()

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        ctx = dict(out["context"], trace=session.reduced,
                   device_kind=devs[0].device_kind,
                   window_compiles=session.window_compiles)
        for m in wanted:
            v = readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(out["end_to_end"], setup_s=session.setup_s)
        for m in wanted:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    result: Dict[str, Any] = {
        "correct": bool(out["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if trace and session.reduced is not None:
        device["busy_s"] = session.reduced["busy_s"]
        device["window_s"] = session.reduced["window_s"]
        result["breakdown"] = _breakdown(session.reduced)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out["checks"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = catalog.benchmark(os.path.join(CHECKOUT, "BENCHMARK.json"))
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"perfbench: {e}; no result", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
