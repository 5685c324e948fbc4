"""Serving driver: continuous-batching engine on the host's devices.

Loads (or random-inits) a model, spins the ServeEngine over a synthetic
request stream, reports throughput/latency percentiles, and runs the FIGMN
OOD monitor over prompt embeddings (the paper's algorithm on the serving
path) as a ``repro.fleet.FleetCoordinator``: request features are hash-
sharded across N StreamRuntime replicas (chunked ingestion, lifecycle
budget, drift detection per shard), periodically consolidated into one
global mixture, and OOD scores are served from that read-only snapshot so
the serving path never blocks on ingestion.  At production scale the same
fleet runs with one replica per serving pod.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --requests 16 --slots 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.api import Mixture, MixtureSpec
from repro.checkpoint import CheckpointManager
from repro.core import figmn
from repro.core.types import FIGMNConfig
from repro.fleet import AutoscaleConfig, FleetConfig
from repro.ft import RetryPolicy, SupervisorConfig
from repro.models import transformer as tr
from repro.obs import export as obs_export
from repro.obs import trace as obs_trace
from repro.rpc import RpcConfig
from repro.serve.engine import Request, ServeEngine
from repro.stream import DriftConfig, LifecycleConfig, RuntimeConfig, costmodel


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a training checkpoint")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ood-replicas", type=int, default=2,
                    help="stream-fleet replicas for the OOD monitor "
                         "(with --ood-autoscale: the maximum)")
    ap.add_argument("--ood-autoscale", action="store_true",
                    help="let the OOD fleet autoscale from 1 replica up "
                         "to --ood-replicas off its own telemetry "
                         "(load skew / budget pressure / drift rate)")
    ap.add_argument("--ood-workers", type=int, default=0, metavar="N",
                    help="run the OOD fleet's replicas as N WORKER "
                         "PROCESSES over repro.rpc instead of threads "
                         "(0 = threads; overrides --ood-replicas). Each "
                         "worker hosts one StreamRuntime; shards ingest "
                         "in parallel, the supervisor's recovery ladder "
                         "gains the worker_dead failure class, and the "
                         "autoscaler allocates/releases processes at "
                         "consolidation boundaries")
    ap.add_argument("--ood-transport", choices=("tcp", "unix"),
                    default="tcp",
                    help="worker RPC transport (with --ood-workers): "
                         "tcp = 127.0.0.1 loopback, unix = socket file")
    ap.add_argument("--ood-supervise", action="store_true",
                    help="run the OOD fleet under the FleetSupervisor "
                         "(repro.ft): heartbeat watchdog per replica, "
                         "chunk retry with backoff+jitter, and the "
                         "quarantine → re-route → checkpoint-restore "
                         "recovery ladder with exact mass accounting")
    ap.add_argument("--ood-heartbeat-timeout", type=float, default=30.0,
                    metavar="SECONDS",
                    help="supervisor watchdog: quarantine a replica whose "
                         "chunk boundary goes silent this long (must "
                         "clear the first-chunk compile; only with "
                         "--ood-supervise)")
    ap.add_argument("--ood-max-staleness", type=float, default=None,
                    metavar="SECONDS",
                    help="degraded-serving bound: OOD reads fail with "
                         "StalenessExceeded rather than serve a snapshot "
                         "older than this (default: serve any last-good "
                         "snapshot)")
    ap.add_argument("--score-shortlist", type=int, default=0,
                    metavar="C",
                    help="top-C component shortlist for the OOD monitor "
                         "(0 = dense): both the ingest hot path and the "
                         "serving score() drop from O(K·D²) to "
                         "O(K·D + C·D²) per point, exact when C >= K")
    ap.add_argument("--cost-table", default=None, metavar="PATH",
                    help="device-calibrated dispatch cost table "
                         "(benchmarks.figmn_dispatch / "
                         "stream.costmodel.calibrate): the OOD monitor's "
                         "ingest and eq. 27 predict paths route by "
                         "measured cost instead of the static heuristic")
    ap.add_argument("--explain-dispatch", action="store_true",
                    help="print the dispatch decision report for the OOD "
                         "monitor config (chosen path, heuristic "
                         "counterfactual, backing calibration cell, "
                         "roofline bottleneck) and how each candidate "
                         "ranked")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve Prometheus text exposition of the obs "
                         "registry on http://0.0.0.0:PORT/metrics "
                         "(0 = ephemeral port; printed at startup)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable structured spans and write them to PATH "
                         "on exit (.json => Chrome trace_event for "
                         "chrome://tracing / Perfetto; else JSONL)")
    args = ap.parse_args()

    if args.metrics_port is not None:
        server = obs_export.serve_metrics(args.metrics_port)
        print(f"obs: serving /metrics on port "
              f"{server.server_address[1]}")
    if args.trace:
        obs_trace.enable()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    params = tr.init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        step = mgr.latest_step()
        if step is not None:
            print(f"restoring params from step {step}")
            params = mgr.restore(step, {"params": params})["params"]

    engine = ServeEngine(cfg, params, n_slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    t_submit = {}
    reqs = []
    for i in range(args.requests):
        p = rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(4, 24))).astype(np.int32)
        r = Request(rid=i, prompt=p, max_tokens=args.max_new)
        engine.submit(r)
        t_submit[i] = time.perf_counter()
        reqs.append(r)

    t0 = time.perf_counter()
    lat = {}
    while engine.queue or any(s is not None for s in engine.slot_req):
        engine.tick()
        now = time.perf_counter()
        for r in reqs:
            if r.done and r.rid not in lat:
                lat[r.rid] = now - t_submit[r.rid]
    dt = time.perf_counter() - t0
    total = sum(len(r.out_tokens) for r in reqs)
    ls = sorted(lat.values())
    print(f"served {len(reqs)} reqs / {total} tokens in {dt:.1f}s "
          f"({total / dt:.1f} tok/s)")
    print(f"latency p50={ls[len(ls) // 2] * 1e3:.0f}ms "
          f"p95={ls[int(len(ls) * 0.95) - 1] * 1e3:.0f}ms")

    # FIGMN OOD monitor over prompt-embedding means (first 16 dims), run
    # through the unified estimator API as the mixture session a serving
    # deployment keeps open: the spec resolves to a hash-sharded fleet
    # (or a telemetry-autoscaled one), request features stream through
    # chunked per-replica ingest with lifecycle budgets and drift
    # detection, and every read — density scores AND eq. 27 conditional
    # reconstructions — is served from the read-only consolidated
    # snapshot without ever blocking ingestion.
    emb = np.asarray(params["embed"], np.float32)
    feats = np.stack([emb[r.prompt].mean(0)[:16] for r in reqs])
    gcfg = FIGMNConfig(kmax=8, dim=16, beta=0.1, delta=1.0, vmin=1e9,
                       spmin=0.0, update_mode="exact",
                       # C > 0 flips ALL hot paths sublinear: ingest
                       # dispatches to the "sparse" body and the serving
                       # frontend shortlists both score() and predict()
                       shortlist_c=max(args.score_shortlist, 0),
                       sigma_ini=figmn.sigma_from_data(
                           jnp.asarray(feats), 1.0))
    cost_table = costmodel.CostTable.load(args.cost_table) \
        if args.cost_table else None
    chunk = max(args.requests // 4, 4)
    if args.explain_dispatch:
        print(costmodel.explain(gcfg, chunk=chunk,
                                cost_table=cost_table))
    monitor = Mixture(MixtureSpec(
        model=gcfg,
        tier="autoscaled" if args.ood_autoscale else "fleet",
        cost_table=cost_table,
        runtime=RuntimeConfig(
            chunk=chunk,
            lifecycle=LifecycleConfig(k_budget=8, every=4),
            drift=DriftConfig(window=8, threshold=8.0,
                              response="inflate")),
        fleet=FleetConfig(
            n_replicas=(args.ood_workers if args.ood_workers > 0
                        else (1 if args.ood_autoscale
                              else args.ood_replicas)),
            placement="process" if args.ood_workers > 0 else "thread",
            rpc=(RpcConfig(transport=args.ood_transport)
                 if args.ood_workers > 0 else None),
            router="hash", consolidate_every=1, global_kmax=8,
            autoscale=AutoscaleConfig(
                min_replicas=1,
                max_replicas=max(args.ood_workers, args.ood_replicas, 1),
                cooldown=1) if args.ood_autoscale else None,
            supervisor=SupervisorConfig(
                heartbeat_timeout_s=args.ood_heartbeat_timeout,
                retry=RetryPolicy(seed=args.seed))
            if args.ood_supervise else None,
            max_staleness_s=args.ood_max_staleness)))
    if args.metrics_port is not None and args.ood_workers > 0:
        # one aggregated /metrics: the coordinator's registry merged with
        # every worker process's scraped registry (mergeable histograms)
        server.RequestHandlerClass.extra_sources = tuple(
            monitor.engine.worker_metric_sources())
    monitor.partial_fit(feats)
    summary = monitor.summary()
    # snapshot reads — non-blocking w.r.t. ingestion (score_async /
    # predict_async exist for callers that also want off their own thread)
    scores = monitor.score_samples(feats)
    # eq. 27 on the serving path: reconstruct the last embedding feature
    # from the rest — the residual is a per-request drift/corruption probe.
    # return_var adds the conditional variance off the same cached factor
    # bundle (one extra Schur term), turning the raw residual into a
    # CALIBRATED z-score: |x̂−x|/σ ≫ 1 flags a corrupted request even when
    # the absolute residual is small in a tight regime.
    recon, rvar = monitor.predict(feats[:, :-1], targets=[gcfg.dim - 1],
                                  return_var=True)
    print(f"FIGMN OOD monitor: {monitor!r}")
    resid = float(jnp.mean(jnp.abs(recon[:, 0] - feats[:, -1])))
    zscore = float(jnp.mean(jnp.abs(recon[:, 0] - feats[:, -1])
                            / jnp.sqrt(jnp.maximum(rvar[:, 0], 1e-12))))
    monitor.close()
    shortcut = (f"shortlist C={gcfg.shortlist_c}, "
                if gcfg.shortlist_c > 0 else "")
    if args.ood_supervise:
        shortcut += (f"supervised (quarantined="
                     f"{summary.get('quarantined_replicas', [])}, "
                     f"recoveries={summary.get('recoveries', 0)}, "
                     f"lost={summary.get('supervisor_points_lost', 0)}), ")
    print(f"FIGMN OOD fleet active ({summary['replicas']} replicas, "
          f"{shortcut}router load {summary['router_load']}): "
          f"in-dist logp median "
          f"{float(jnp.median(scores)):.1f} over {len(reqs)} requests "
          f"({summary['points_per_s']:.0f} feats/s, "
          f"global K={summary['global_active_k']}, "
          f"snapshot v{summary['snapshot_version']}, "
          f"drift alarms={summary['drift_alarms']}, "
          f"scale events={summary['scale_ups']}+{summary['scale_downs']} "
          f"epoch={summary['epoch']}, "
          f"eq27 |x̂₁₅−x₁₅| = {resid:.3f}, z = {zscore:.2f})")

    if args.trace:
        tracer = obs_trace.disable()
        if args.trace.endswith(".json"):
            tracer.export_chrome(args.trace)
        else:
            tracer.export_jsonl(args.trace)
        print(f"obs: wrote {len(tracer.spans())} spans to {args.trace}")


if __name__ == "__main__":
    main()
