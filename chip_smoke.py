#!/usr/bin/env python3
"""Bring-up check of the density service on one TPU chip.

    python chip_smoke.py

Drives the service's main path through the entry points a user calls
(``repro.api.Mixture`` over the runtime and fleet tiers) at the paper's
high-D shape (D = 784, Table 1) and checks what comes out:

  agree   K = 64: chunked ingest, score and eq. 27 predict on held-out
          points against ``figmn.fit`` + ``score_batch`` +
          ``inference.predict_batch`` on the host's CPU in float32 at
          `highest` matmul precision; chunked ≡ one-shot on the chip.
  size    K = 1024 (Λ takes 2.52 GB) through the dense scan: a few chunks,
          then score and predict requests; all finite; peak HBM.
  sparse  the same with a 16-wide shortlist, against the size phase.
  vmem    the OOD monitor of ``repro.launch.serve`` on the fleet tier must
          resolve to the VMEM-resident kernel; then the kernel forced at
          the largest pool its guard admits at D = 784, against the scan.
  fleet   two thread replicas at K = 64: ingest, consolidate, serve.

Each phase prints its shapes, its compile, run and wall seconds, and one
line per check with the value and the limit.  compile_s sums JAX's trace,
lowering and compile events (cache reads included) over all threads —
fleet workers compile in parallel, so it can exceed wall_s; run_s is
wall_s − compile_s, floored at 0.  Exits non-zero, printing no
result, when JAX finds no TPU or any check fails; a passing run ends with
the line ``{"ok": true, "device": {...}}``.  Streams come from
``repro.data.gmm_streams`` with fixed seeds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

#: the paper's MNIST shape (configs/figmn_paper.py, Table 1)
D = 784
#: §4 accuracy setting (configs/figmn_paper.py ACC_BETA)
BETA = 0.001

#: agree: |Δ log p| / |log p| on held-out points, chip vs CPU reference.
#: At D = 784 a log-density is about −1400, where one float32 ulp is
#: 1.2e-4 nats; the posteriors that weight each of the 512 sequential
#: rank-one updates are exponentials of differences of such numbers, so
#: a few ulps of difference in the TPU's transcendentals or reduction
#: order move a point with split responsibility by ~1e-4 relative, and
#: later updates carry it.  Measured on a v5e: 1.8e-3, the same at the
#: default and at `highest` matmul precision (the learning step's
#: matvecs are float32 either way).
LL_REL_TOL = 5e-3
#: agree: |Δ x̂| of the eq. 27 prediction, in units of the target's std
#: (the same float32 error, carried through the Schur complement).
PRED_STD_TOL = 5e-2
#: sparse vs dense held-out mean log-density (tests/test_shortlist.py)
SPARSE_LL_TOL = 0.5
#: vmem: |Δ log p| / |log p| of the kernel against the scan on the chip
#: after 768 updates.  Same math, float32 reductions in another order
#: (the kernel's MXU matvec against XLA's), so the bound of ``agree``:
#: another order of the same float32 updates.  Measured on a v5e: Λ
#: agreed to 5.7e-7 relative after 256 points, log p to 9.8e-5 after 768.
VMEM_LL_REL_TOL = LL_REL_TOL
#: fleet: relative change of the active sp mass through consolidation
#: (exact up to float32 rounding of the merges)
MASS_REL_TOL = 1e-5

FAILED = []
_COMPILE_S = [0.0]
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def _on_duration(event, duration, **_):
    if event in _COMPILE_EVENTS:
        _COMPILE_S[0] += duration


def check(phase, name, value, limit, ok):
    print(f"  [{phase}] {'PASS' if ok else 'FAIL'} {name} = {value} "
          f"(limit: {limit})", flush=True)
    if not ok:
        FAILED.append(f"{phase}:{name}")


@contextlib.contextmanager
def timed(name, **shapes):
    print(f"phase {name}: " + " ".join(f"{k}={v}" for k, v in
                                       shapes.items()), flush=True)
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = _COMPILE_S[0] - c0
    print(f"phase {name}: compile_s={comp:.3f} "
          f"run_s={max(wall - comp, 0.0):.3f} wall_s={wall:.3f}", flush=True)


def stream(n, d, classes, seed):
    from repro.data import gmm_streams
    return gmm_streams.gaussian_classes(n, d, classes, seed=seed)[0]


def config(x, kmax, delta=1.0, **kw):
    from repro.core.types import FIGMNConfig
    std = x.std(axis=0)
    sigma = (delta * np.where(std <= 1e-12, 1.0, std)).astype(np.float32)
    return FIGMNConfig(kmax=kmax, dim=x.shape[1], beta=BETA, delta=delta,
                       vmin=1e9, spmin=0.0, update_mode="exact",
                       sigma_ini=sigma, **kw)


def mixture(cfg, tier="runtime", path="auto", chunk=128, **kw):
    from repro.api import Mixture, MixtureSpec
    from repro.stream import RuntimeConfig
    return Mixture(MixtureSpec(model=cfg, tier=tier, runtime=RuntimeConfig(
        chunk=chunk, path=path), **kw))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def finite(*arrays):
    return all(bool(np.isfinite(np.asarray(a)).all()) for a in arrays)


def agree(dev):
    from repro.core import figmn, inference
    x = stream(512 + 128, D, 10, seed=0)
    tr, ho = x[:512], x[512:]
    cfg = config(tr, 64)
    with timed("agree", D=D, kmax=64, n=512, chunks="4x128", held_out=128):
        mix = mixture(cfg)
        for a in range(0, 512, 128):
            mix.partial_fit(tr[a:a + 128])
        ll = np.asarray(mix.score_samples(ho))
        pred = np.asarray(mix.predict(ho[:, :-1], targets=[D - 1]))[:, 0]
        state = mix.state
        one_shot = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(tr))
        jax.block_until_ready((ll, pred, one_shot))
        with jax.default_device(jax.devices("cpu")[0]), \
                jax.default_matmul_precision("highest"):
            ref = figmn.fit(cfg, figmn.init_state(cfg), jnp.asarray(tr))
            ll_ref = np.asarray(figmn.score_batch(cfg, ref, jnp.asarray(ho)))
            pred_ref = np.asarray(inference.predict_batch(
                cfg, ref, jnp.asarray(ho[:, :-1]), [D - 1]))[:, 0]
    check("agree", "components_created",
          f"{int(state.n_created)} (reference {int(ref.n_created)})",
          "equal", int(state.n_created) == int(ref.n_created))
    check("agree", "components_active",
          f"{int(state.n_active)} (reference {int(ref.n_active)})",
          "equal", int(state.n_active) == int(ref.n_active))
    r = rel(ll, ll_ref)
    check("agree", "heldout_logp_max_rel_err", f"{r:.3e}",
          f"<= {LL_REL_TOL:g}", r <= LL_REL_TOL)
    scale = float(np.std(ho[:, -1]))
    e = float(np.max(np.abs(pred - pred_ref))) / scale
    check("agree", "predict_max_err_in_target_std", f"{e:.3e}",
          f"<= {PRED_STD_TOL:g}", e <= PRED_STD_TOL)
    same = all(bool(np.array_equal(np.asarray(getattr(state, f)),
                                   np.asarray(getattr(one_shot, f))))
               for f in ("mu", "lam", "logdet", "sp", "v", "active"))
    print(f"  [agree] chunked_equals_one_shot_bitwise = {same}", flush=True)


def size(dev):
    x = stream(96 + 64, D, 10, seed=1)
    tr, ho = x[:96], x[96:]
    cfg = config(tr, 1024)
    with timed("size", D=D, kmax=1024, lam_gb=f"{1024 * D * D * 4 / 1e9:.2f}",
               chunks="3x32", requests="4x16"):
        mix = mixture(cfg, chunk=32)
        mix.partial_fit(tr)
        lls = [np.asarray(mix.score_samples(ho[i:i + 16]))
               for i in range(0, 64, 16)]
        preds = [np.asarray(mix.predict(ho[i:i + 16, :-1], targets=[D - 1]))
                 for i in range(0, 64, 16)]
    check("size", "path", mix.engine.path, "scan", mix.engine.path == "scan")
    check("size", "outputs_finite", finite(*lls, *preds), "True",
          finite(*lls, *preds))
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  [size] peak_bytes_in_use = {peak}; active components = "
          f"{int(mix.n_active)}", flush=True)
    return float(np.mean(np.concatenate(lls)))


def sparse(dev, dense_mean_ll):
    x = stream(96 + 64, D, 10, seed=1)
    tr, ho = x[:96], x[96:]
    cfg = config(tr, 1024, shortlist_c=16)
    with timed("sparse", D=D, kmax=1024, shortlist_c=16, chunks="3x32",
               requests="4x16"):
        mix = mixture(cfg, chunk=32)
        mix.partial_fit(tr)
        lls = [np.asarray(mix.score_samples(ho[i:i + 16]))
               for i in range(0, 64, 16)]
        preds = [np.asarray(mix.predict(ho[i:i + 16, :-1], targets=[D - 1]))
                 for i in range(0, 64, 16)]
    check("sparse", "path", mix.engine.path, "sparse",
          mix.engine.path == "sparse")
    check("sparse", "outputs_finite", finite(*lls, *preds), "True",
          finite(*lls, *preds))
    gap = abs(float(np.mean(np.concatenate(lls))) - dense_mean_ll)
    check("sparse", "heldout_mean_logp_gap_to_dense", f"{gap:.4f}",
          f"< {SPARSE_LL_TOL}", gap < SPARSE_LL_TOL)


def vmem(dev):
    from repro import devices
    from repro.api import Mixture, MixtureSpec
    from repro.fleet import FleetConfig
    from repro.kernels import figmn_stream
    from repro.stream import (DriftConfig, LifecycleConfig, RuntimeConfig,
                              ingest)
    # the OOD monitor as launch/serve.py builds it
    feats = stream(64, 16, 4, seed=2)
    cfg = dataclasses.replace(config(feats, 8), beta=0.1)
    chunk = 16
    with timed("vmem_ood", D=16, kmax=8, tier="fleet", replicas=2,
               chunk=chunk, n=64):
        mon = Mixture(MixtureSpec(
            model=cfg, tier="fleet",
            runtime=RuntimeConfig(
                chunk=chunk, lifecycle=LifecycleConfig(k_budget=8, every=4),
                drift=DriftConfig(window=8, threshold=8.0,
                                  response="inflate")),
            fleet=FleetConfig(n_replicas=2, router="hash",
                              consolidate_every=1, global_kmax=8)))
        mon.partial_fit(feats)
        scores = np.asarray(mon.score_samples(feats))
        recon = np.asarray(mon.predict(feats[:, :-1], targets=[15]))
        paths = [r.path for r in mon.engine.replicas]
        used = sorted({m.path for r in mon.engine.replicas
                       for m in r.telemetry.history})
        hlo = figmn_stream.figmn_stream_pallas.lower(
            jnp.asarray(feats[:chunk]), *(jnp.zeros(s, jnp.float32) for s in
                                          ((8, 16), (8, 16, 16), (8,), (8,))),
            jnp.ones((8,), jnp.int32), jnp.ones((1,), jnp.float32), dim=16,
            n_points=chunk, vmem_limit=ingest.vmem_budget_for()
        ).compile().as_text()
        mon.close()
    check("vmem", "ood_replica_paths", paths, "all 'vmem'",
          paths == ["vmem", "vmem"])
    check("vmem", "ood_chunk_paths_used", used, "includes 'vmem'",
          "vmem" in used)
    check("vmem", "ood_hlo_has_tpu_custom_call",
          "tpu_custom_call" in hlo, "True", "tpu_custom_call" in hlo)
    check("vmem", "ood_outputs_finite", finite(scores, recon), "True",
          finite(scores, recon))

    # the kernel forced at the largest pool the guard admits at D = 784
    budget = devices.lookup(dev.device_kind).vmem_bytes
    n_chunk = 256
    x = stream(4 * n_chunk + 128, D, 10, seed=3)
    tr, ho = x[:4 * n_chunk], x[4 * n_chunk:]
    k = 8
    while ingest.select_path(config(tr, k + 8, delta=3.0), device="tpu",
                             vmem_budget=budget, chunk=n_chunk) == "vmem":
        k += 8
    # delta = 3 widens the initial components so that, after the scan
    # forms the pool on the first chunk, every later point is an update:
    # the kernel cannot create components, so only then are the two
    # paths the same computation
    cfg = config(tr, k, delta=3.0)
    kb = figmn_stream.vmem_bytes(k, D, n_chunk)
    with timed("vmem_boundary", D=D, kmax=k, chunk=n_chunk,
               n=4 * n_chunk, kernel_vmem_bytes=kb, budget=budget):
        got = {}
        for path in ("vmem", "scan"):
            mix = mixture(cfg, path=path, chunk=n_chunk)
            mix.partial_fit(tr)
            got[path] = (mix, np.asarray(mix.score_samples(ho)))
    mv, ms = got["vmem"][0], got["scan"][0]
    used = [m.path for m in mv.engine.telemetry.history]
    check("vmem", "boundary_chunk_paths", used,
          "scan once, then vmem", used == ["scan"] + ["vmem"] * 3)
    check("vmem", "boundary_components_created",
          f"{int(mv.state.n_created)} (scan {int(ms.state.n_created)})",
          "equal", int(mv.state.n_created) == int(ms.state.n_created))
    r = rel(got["vmem"][1], got["scan"][1])
    check("vmem", "boundary_heldout_logp_max_rel_err_vs_scan", f"{r:.3e}",
          f"<= {VMEM_LL_REL_TOL:g}", r <= VMEM_LL_REL_TOL)


def fleet(dev):
    from repro.fleet import FleetConfig
    from repro.fleet.consolidate import sp_mass
    x = stream(512 + 64, D, 10, seed=4)
    tr, ho = x[:512], x[512:]
    cfg = config(tr, 64)
    with timed("fleet", D=D, kmax=64, replicas=2, placement="thread",
               n=512, chunk=128):
        mix = mixture(cfg, tier="fleet", chunk=128, fleet=FleetConfig(
            n_replicas=2, router="hash", consolidate_every=1,
            global_kmax=64))
        mix.partial_fit(tr)
        eng = mix.engine
        mass_in = sum(sp_mass(r.state) for r in eng.replicas)
        snap = eng.global_state
        ll = np.asarray(mix.score_samples(ho))
        pred = np.asarray(mix.predict(ho[:, :-1], targets=[D - 1]))
        summary = mix.summary()
        mix.close()
    check("fleet", "snapshot_version", summary["snapshot_version"], ">= 1",
          summary["snapshot_version"] >= 1)
    check("fleet", "global_active_k", summary["global_active_k"], ">= 1",
          summary["global_active_k"] >= 1)
    drift = abs(sp_mass(snap) - mass_in) / mass_in
    check("fleet", "consolidation_sp_mass_rel_change", f"{drift:.3e}",
          f"<= {MASS_REL_TOL:g}", drift <= MASS_REL_TOL)
    check("fleet", "outputs_finite", finite(ll, pred), "True",
          finite(ll, pred))


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro import compile_cache
    print(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache: "
          f"{compile_cache.enable()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    agree(dev)
    sparse(dev, size(dev))
    vmem(dev)
    fleet(dev)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: "
              f"{', '.join(FAILED)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
