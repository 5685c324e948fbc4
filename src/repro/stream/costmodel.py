"""Device-calibrated dispatch cost model — measured ``select_path``.

ROADMAP item 2 calls the VMEM heuristic in ``ingest.select_path`` "a
guess".  This module replaces the guesswork with measurement while keeping
the guess as the bit-compatible fallback:

  * ``calibrate`` times every dispatch path (scan / sparse / vmem ingest;
    dense / sparse score and eq. 27 predict) over a (K, D, C, chunk) grid
    on the ACTUAL backend — compile-excluded, ``block_until_ready``-fenced,
    median-of-R (obs.prof) — and pairs each measurement with an
    HLO-derived roofline prediction (distributed.hlo_analysis on the
    compiled module), producing a ``CostTable``.
  * ``CostTable`` is persisted as versioned JSON (obs.export.to_json),
    keyed by ``(device_kind, jax_version)`` so a table calibrated on one
    machine never silently drives decisions on different hardware, and
    mergeable across runs/devices (same-cell conflicts keep the faster
    measurement — re-calibration can only sharpen a table).
  * ``decide`` / ``resolve_path`` are what the runtime, fleet coordinator
    and Mixture facade consult at resolve time: forced paths stay forced;
    with no table (or no cells for this device key) the decision IS
    ``ingest.select_path``'s heuristic, bit-compatibly; with a table, the
    path with the smallest measured per-point seconds wins among the
    SAFE candidates — the vmem candidacy guard (exact update mode, the
    kernel's VMEM footprint ≤ the device's VMEM, TPU backend) is a
    launch-correctness constraint and survives calibration, so an
    oversized working set can never select "vmem" no matter what a table
    claims.
  * ``resolve_path`` additionally exports the decision layer:
    ``figmn_dispatch_decisions_total{path,reason}``,
    ``figmn_vmem_budget_bytes``, ``figmn_dispatch_predicted_seconds`` and
    a ``dispatch.resolve`` span in the obs trace stream.

The VMEM budget comes from the device's entry in ``repro.devices``, keyed
by ``device_kind``; an unknown kind raises instead of guessing.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import devices
from repro.core import figmn, shortlist
from repro.core.types import FIGMNConfig
from repro.kernels import figmn_stream
from repro.obs import export as obs_export
from repro.obs import prof
from repro.obs import registry as obs_registry
from repro.obs.trace import span
from repro.stream import ingest

#: bump when the CostTable cell/envelope shape changes; ``CostTable.load``
#: refuses versions it does not know (misparsing a table would silently
#: redirect production dispatch).
TABLE_VERSION = 1

def resolve_backend(device: Optional[str] = None) -> str:
    """The backend a dispatch decision is for: an explicit platform name
    ("cpu"/"gpu"/"tpu") wins, else the process default — threading this
    through configs is what makes dispatch device-aware instead of keyed
    off one global."""
    return device if device else jax.default_backend()


def device_key(device: Optional[str] = None) -> str:
    """``"<device_kind>|jax-<version>"`` — the CostTable entry key.

    device_kind (e.g. "TPU v4", "cpu") pins the hardware; the jax version
    pins the compiler generation (the same path can flip winners across
    XLA releases).  A checkpoint restored on different hardware therefore
    re-resolves from its own entries — or falls back to the heuristic —
    instead of replaying a stale decision.
    """
    kind = devices.device_kind(resolve_backend(device))
    return f"{kind}|jax-{jax.__version__}"


def resolve_vmem_budget(explicit: Optional[int] = None,
                        device: Optional[str] = None) -> Tuple[int, str]:
    """→ (bytes, source) with source ∈ {"config", "device"}.

    An explicit budget always wins (operator override).  Otherwise the
    device's entry in ``repro.devices`` (0 on the CPU, which has no VMEM);
    an unknown device kind raises.
    """
    if explicit is not None:
        return int(explicit), "config"
    return ingest.vmem_budget_for(resolve_backend(device)), "device"


# ---------------------------------------------------------------------------
# CostTable
# ---------------------------------------------------------------------------

def _cell_key(cell: Dict) -> Tuple:
    return (cell["kind"], cell["path"], int(cell["k"]), int(cell["d"]),
            int(cell.get("c", 0)), int(cell["n"]))


def _log_dist(cell: Dict, k: int, d: int, c: int, n: int) -> float:
    """Nearest-cell metric: squared distance in log1p space over the
    (K, D, C, n) axes — multiplicative regimes, not absolute deltas,
    decide which calibration point a config resembles."""
    tot = 0.0
    for have, want in ((cell["k"], k), (cell["d"], d),
                       (cell.get("c", 0), c), (cell["n"], n)):
        tot += (math.log1p(float(have)) - math.log1p(float(want))) ** 2
    return tot


class CostTable:
    """Measured per-path costs, keyed by device, mergeable across runs.

    ``entries`` maps ``device_key()`` strings to lists of cells::

        {"kind": "ingest"|"score"|"predict", "path": str,
         "k": int, "d": int, "c": int, "n": int,
         "measured_s": float, "per_point_s": float,
         "hlo": {"flops": ..., "traffic_bytes": ...} | None,
         "compute_s"/"memory_s"/"predicted_s": float | None,
         "bottleneck": "compute"|"memory" | None}
    """

    def __init__(self, entries: Optional[Dict[str, List[Dict]]] = None,
                 meta: Optional[Dict] = None):
        self.entries: Dict[str, List[Dict]] = {
            k: list(v) for k, v in (entries or {}).items()}
        self.meta: Dict = dict(meta or {})

    # -- construction --------------------------------------------------

    def add_cell(self, dkey: str, cell: Dict) -> None:
        """Insert/replace one cell (same cell key ⇒ keep the faster
        measurement — the merge rule, applied incrementally)."""
        cells = self.entries.setdefault(dkey, [])
        key = _cell_key(cell)
        for i, have in enumerate(cells):
            if _cell_key(have) == key:
                if cell["measured_s"] < have["measured_s"]:
                    cells[i] = dict(cell)
                return
        cells.append(dict(cell))

    def merge(self, other: "CostTable") -> "CostTable":
        """Union of device keys; duplicate cells keep the faster
        measurement (medians only over-estimate under interference, so
        min is the honest combinator).  Returns a NEW table."""
        out = CostTable(self.entries, self.meta)
        for dkey, cells in other.entries.items():
            for cell in cells:
                out.add_cell(dkey, cell)
        merged_meta = dict(other.meta)
        merged_meta.update(out.meta)   # self.meta wins on conflicts
        out.meta = merged_meta
        return out

    # -- lookup --------------------------------------------------------

    def cells(self, dkey: str, kind: Optional[str] = None,
              path: Optional[str] = None) -> List[Dict]:
        return [c for c in self.entries.get(dkey, ())
                if (kind is None or c["kind"] == kind)
                and (path is None or c["path"] == path)]

    def lookup(self, dkey: str, kind: str, path: str, *, k: int, d: int,
               c: int = 0, n: int = 1) -> Optional[Dict]:
        """Nearest calibrated cell for (kind, path) in log-(K, D, C, n)
        space; deterministic tie-break on the cell key so equal-distance
        grids resolve identically across processes."""
        cands = self.cells(dkey, kind, path)
        if not cands:
            return None
        return min(cands, key=lambda cell: (_log_dist(cell, k, d, c, n),
                                            _cell_key(cell)))

    def device_keys(self) -> List[str]:
        return sorted(self.entries)

    # -- persistence ---------------------------------------------------

    def to_doc(self) -> Dict:
        return {"cost_table_version": TABLE_VERSION,
                "meta": self.meta, "entries": self.entries}

    def save(self, path: str) -> None:
        obs_export.to_json(path, self.to_doc())

    @classmethod
    def from_doc(cls, doc: Dict) -> "CostTable":
        ver = doc.get("cost_table_version")
        if ver != TABLE_VERSION:
            raise ValueError(
                f"unknown cost table version {ver!r} (this build reads "
                f"version {TABLE_VERSION}); re-calibrate or upgrade")
        return cls(entries=doc.get("entries", {}), meta=doc.get("meta", {}))

    @classmethod
    def load(cls, path: str) -> "CostTable":
        with open(path) as f:
            return cls.from_doc(json.load(f))

    @classmethod
    def from_any(cls, obj) -> Optional["CostTable"]:
        """None | CostTable | path-to-JSON — what configs may carry."""
        if obj is None or isinstance(obj, cls):
            return obj
        if isinstance(obj, str):
            return cls.load(obj)
        if isinstance(obj, dict):
            return cls.from_doc(obj)
        raise TypeError(f"cannot interpret {type(obj).__name__} as a "
                        f"CostTable (want None, CostTable, dict or path)")


# ---------------------------------------------------------------------------
# decisions
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DispatchDecision:
    """One resolved dispatch, with enough context to explain itself."""
    path: str                 # chosen body
    reason: str               # "forced" | "heuristic" | "no_table_entry"
    #                         # | "table"
    heuristic_path: str       # what the PR-6 heuristic would have chosen
    device_key: str
    backend: str
    vmem_budget: int
    vmem_source: str          # "config" | "device"
    per_point_s: Optional[float] = None
    predicted_s: Optional[float] = None   # HLO roofline seconds (cell)
    measured_s: Optional[float] = None
    bottleneck: Optional[str] = None
    cell: Optional[Dict] = None
    candidates: Dict[str, float] = dataclasses.field(default_factory=dict)


def _vmem_candidate_ok(cfg: FIGMNConfig, budget: int, backend: str,
                       chunk: int) -> bool:
    """The launch-correctness guard the vmem kernel requires — identical
    to the heuristic's condition and NOT overridable by a table."""
    return backend == "tpu" and ingest.vmem_fits(cfg, budget, chunk)


def decide(cfg: FIGMNConfig, *, requested: str = "auto", chunk: int = 256,
           vmem_budget: Optional[int] = None, device: Optional[str] = None,
           cost_table=None) -> DispatchDecision:
    """Resolve the ingest dispatch path, table-first, heuristic-fallback.

    Pure (no metrics, no spans) — safe from ``__repr__``s and tests;
    ``resolve_path`` is the recording twin the engines call once per
    construction.  Bit-compat contract (pinned in tests/test_costmodel.py):
    with ``cost_table=None`` the returned ``path`` equals
    ``ingest.select_path(cfg, vmem_budget=..., requested=...)`` exactly,
    for every (cfg, requested, budget, device) combination.
    """
    backend = resolve_backend(device)
    budget, source = resolve_vmem_budget(vmem_budget, device)
    heuristic = ingest.select_path(cfg, vmem_budget=budget,
                                   requested=requested, device=backend,
                                   chunk=chunk)
    dkey = device_key(device)
    base = dict(heuristic_path=heuristic, device_key=dkey, backend=backend,
                vmem_budget=budget, vmem_source=source)
    if requested != "auto":
        return DispatchDecision(path=heuristic, reason="forced", **base)
    table = CostTable.from_any(cost_table)
    if table is None:
        return DispatchDecision(path=heuristic, reason="heuristic", **base)
    candidates = ["scan"]
    if cfg.shortlist_c > 0:
        candidates.append("sparse")
    if _vmem_candidate_ok(cfg, budget, backend, chunk):
        candidates.append("vmem")
    found: Dict[str, Dict] = {}
    for path in candidates:
        c = cfg.shortlist_c if path == "sparse" else 0
        cell = table.lookup(dkey, "ingest", path, k=cfg.kmax, d=cfg.dim,
                            c=c, n=chunk)
        if cell is not None:
            found[path] = cell
    if not found:
        return DispatchDecision(path=heuristic, reason="no_table_entry",
                                **base)
    best = min(found, key=lambda p: (found[p]["per_point_s"], p))
    cell = found[best]
    return DispatchDecision(
        path=best, reason="table",
        per_point_s=float(cell["per_point_s"]),
        predicted_s=cell.get("predicted_s"),
        measured_s=float(cell["measured_s"]),
        bottleneck=cell.get("bottleneck"), cell=cell,
        candidates={p: float(found[p]["per_point_s"]) for p in found},
        **base)


def resolve_path(cfg: FIGMNConfig, *, requested: str = "auto",
                 chunk: int = 256, vmem_budget: Optional[int] = None,
                 device: Optional[str] = None, cost_table=None,
                 registry: Optional[obs_registry.Registry] = None
                 ) -> DispatchDecision:
    """``decide`` + the observability exports (one call per engine build):
    decision counter, VMEM-budget gauge, predicted-seconds gauge and a
    ``dispatch.resolve`` span in the trace stream."""
    d = decide(cfg, requested=requested, chunk=chunk,
               vmem_budget=vmem_budget, device=device,
               cost_table=cost_table)
    reg = registry or obs_registry.default_registry()
    reg.counter("figmn_dispatch_decisions_total",
                "dispatch resolutions by chosen path and decision source",
                {"path": d.path, "reason": d.reason}).inc()
    reg.gauge("figmn_vmem_budget_bytes",
              "VMEM budget the kernel-launch guard compares against "
              "(source: config override or the device's table entry)"
              ).set(d.vmem_budget)
    if d.per_point_s is not None:
        reg.gauge("figmn_dispatch_predicted_seconds",
                  "cost-table expected seconds for one chunk on the "
                  "chosen path (pair with figmn_dispatch_measured_seconds)"
                  ).set(d.per_point_s * chunk)
    with span("dispatch.resolve", path=d.path, reason=d.reason,
              heuristic=d.heuristic_path, backend=d.backend,
              vmem_budget=d.vmem_budget):
        pass
    return d


def decide_predict(cfg: FIGMNConfig, *, c: int, n: int = 512,
                   device: Optional[str] = None, cost_table=None
                   ) -> DispatchDecision:
    """The dense-vs-sparse eq. 27 predict routing (the ``c`` switch in
    ``inference.predict_batch_routed``), table-aware.

    Heuristic (and the c<=0 / no-table behaviour, bit-compat with PR 6):
    sparse whenever a shortlist width was resolved.  With a table, the
    measured faster of {dense, sparse@c} wins — at small K the bound
    pass + gather overhead can beat its own savings, which is exactly
    the regime flip a heuristic cannot see.
    """
    backend = resolve_backend(device)
    dkey = device_key(device)
    heuristic = "sparse" if c > 0 else "dense"
    base = dict(heuristic_path=heuristic, device_key=dkey, backend=backend,
                vmem_budget=0, vmem_source="config")
    if c <= 0:
        return DispatchDecision(path="dense", reason="forced", **base)
    table = CostTable.from_any(cost_table)
    if table is None:
        return DispatchDecision(path=heuristic, reason="heuristic", **base)
    found: Dict[str, Dict] = {}
    for path, cc in (("dense", 0), ("sparse", c)):
        cell = table.lookup(dkey, "predict", path, k=cfg.kmax, d=cfg.dim,
                            c=cc, n=n)
        if cell is not None:
            found[path] = cell
    if len(found) < 2:
        return DispatchDecision(path=heuristic, reason="no_table_entry",
                                **base)
    best = min(found, key=lambda p: (found[p]["per_point_s"], p))
    cell = found[best]
    return DispatchDecision(
        path=best, reason="table",
        per_point_s=float(cell["per_point_s"]),
        predicted_s=cell.get("predicted_s"),
        measured_s=float(cell["measured_s"]),
        bottleneck=cell.get("bottleneck"), cell=cell,
        candidates={p: float(found[p]["per_point_s"]) for p in found},
        **base)


def resolve_predict(cfg: FIGMNConfig, *, c: int, n: int = 512,
                    device: Optional[str] = None, cost_table=None,
                    registry: Optional[obs_registry.Registry] = None
                    ) -> DispatchDecision:
    """Recording twin of ``decide_predict`` (path label prefixed
    ``predict_`` so serving decisions don't alias ingest ones)."""
    d = decide_predict(cfg, c=c, n=n, device=device, cost_table=cost_table)
    reg = registry or obs_registry.default_registry()
    reg.counter("figmn_dispatch_decisions_total",
                "dispatch resolutions by chosen path and decision source",
                {"path": f"predict_{d.path}", "reason": d.reason}).inc()
    return d


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

#: (K, D, (C...)) calibration grid; chunk sizes and serve batch ride along.
DEFAULT_GRID: Tuple = ((64, 16, (8,)), (256, 32, (8, 16)))
SMOKE_GRID: Tuple = ((16, 8, (4,)),)


def _synth(n: int, d: int, modes: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 8.0, (modes, d))
    x = centers[rng.integers(0, modes, n)] + rng.normal(0, 1.0, (n, d))
    return x.astype(np.float32)


def _calib_cfg(x: np.ndarray, kmax: int, c: int = 0) -> FIGMNConfig:
    return FIGMNConfig(kmax=kmax, dim=x.shape[1], beta=0.1, delta=1.0,
                       vmin=1e9, spmin=0.0, update_mode="exact",
                       shortlist_c=c,
                       sigma_ini=figmn.sigma_from_data(jnp.asarray(x), 1.0))


def _copy_state(state):
    return jax.tree_util.tree_map(jnp.copy, state)


def _mk_cell(kind: str, path: str, k: int, d: int, c: int, n: int,
             measured_s: float, hlo: Optional[Dict], device_kind: str
             ) -> Dict:
    cell = {"kind": kind, "path": path, "k": int(k), "d": int(d),
            "c": int(c), "n": int(n), "measured_s": float(measured_s),
            "per_point_s": float(measured_s) / max(int(n), 1),
            "hlo": ({"flops": hlo.get("flops", 0.0),
                     "traffic_bytes": hlo.get("traffic_bytes", 0.0)}
                    if hlo else None),
            "compute_s": None, "memory_s": None, "predicted_s": None,
            "bottleneck": None}
    terms = prof.roofline_terms(hlo, device_kind)
    if terms:
        cell.update(terms)
    return cell


def calibrate(grid: Sequence = DEFAULT_GRID, *,
              chunks: Sequence[int] = (256,), n_serve: int = 1024,
              repeats: int = 3, device: Optional[str] = None,
              include_vmem: Optional[bool] = None, seed: int = 0,
              base: Optional[CostTable] = None,
              verbose: bool = False) -> CostTable:
    """Measure every dispatch path over a (K, D, C, chunk) grid → table.

    Each (K, D) point fits a warm pool once (steady-state dispatch serves
    a formed mixture, not the creation burst), then times each body from
    copies of that pool (the fit jits donate their state).  ``include_vmem``
    defaults to TPU-only: in interpret mode the Pallas body is a
    correctness path whose timing would poison the table.  ``base`` merges
    the new cells into an existing table (cross-run accumulation).
    """
    from repro.core import inference   # predict kernels (no import cycle)

    backend = resolve_backend(device)
    dkey = device_key(device)
    dev = devices.device_kind(backend)
    if include_vmem is None:
        include_vmem = backend == "tpu"
    table = CostTable(meta={"backend": backend, "device_key": dkey,
                            "jax_version": jax.__version__,
                            "grid": [list(g[:2]) + [list(g[2])]
                                     for g in grid],
                            "chunks": list(chunks), "n_serve": int(n_serve),
                            "repeats": int(repeats)})

    for kmax, d, cs in grid:
        modes = min(max(kmax // 4, 2), 16)
        warm_n = max(max(chunks), 512)
        xw = _synth(warm_n, d, modes, seed=seed)
        cfg_dense = _calib_cfg(xw, kmax)
        warm = figmn.fit(cfg_dense, figmn.init_state(cfg_dense),
                         jnp.asarray(xw))
        serve = jnp.asarray(_synth(n_serve, d, modes, seed=seed + 11))
        serve_in = serve[:, :d - 1]
        targets = (d - 1,)

        for n in chunks:
            xc = jnp.asarray(xw[:n])

            with span("costmodel.calibrate_cell", k=kmax, d=d, n=n):
                t = prof.median_time(
                    figmn.fit, lambda: (cfg_dense, _copy_state(warm), xc),
                    repeats=repeats)
                hlo = prof.hlo_cost(
                    lambda s, x: figmn.fit(cfg_dense, s, x), warm, xc)
                table.add_cell(dkey, _mk_cell(
                    "ingest", "scan", kmax, d, 0, n, t, hlo, dev))

                for c in cs:
                    cfg_c = dataclasses.replace(cfg_dense, shortlist_c=c)
                    t = prof.median_time(
                        shortlist.fit_sparse,
                        lambda: (cfg_c, _copy_state(warm), xc),
                        repeats=repeats)
                    hlo = prof.hlo_cost(
                        lambda s, x: shortlist.fit_sparse(cfg_c, s, x),
                        warm, xc)
                    table.add_cell(dkey, _mk_cell(
                        "ingest", "sparse", kmax, d, c, n, t, hlo, dev))

                if include_vmem and _vmem_candidate_ok(
                        cfg_dense, resolve_vmem_budget(None, device)[0],
                        backend, n):
                    t = prof.median_time(
                        ingest.fit_chunk_vmem,
                        lambda: (cfg_dense, _copy_state(warm), xc),
                        repeats=repeats)
                    table.add_cell(dkey, _mk_cell(
                        "ingest", "vmem", kmax, d, 0, n, t, None, dev))

        # serving reads: dense vs sparse score, dense vs sparse predict
        with span("costmodel.calibrate_serve", k=kmax, d=d, n=n_serve):
            t = prof.median_time(ingest.score_batch_jit,
                                 lambda: (cfg_dense, warm, serve),
                                 repeats=repeats)
            hlo = prof.hlo_cost(
                lambda s, x: figmn.score_batch(cfg_dense, s, x),
                warm, serve)
            table.add_cell(dkey, _mk_cell(
                "score", "dense", kmax, d, 0, n_serve, t, hlo, dev))

            t = prof.median_time(
                inference.predict_batch,
                lambda: (cfg_dense, warm, serve_in, targets),
                repeats=repeats)
            hlo = prof.hlo_cost(
                lambda s, x: inference._predict_dense_jit(
                    inference._factors_jit(cfg_dense, s, targets),
                    s.sp, s.active, x), warm, serve_in)
            table.add_cell(dkey, _mk_cell(
                "predict", "dense", kmax, d, 0, n_serve, t, hlo, dev))

            for c in cs:
                cfg_c = dataclasses.replace(cfg_dense, shortlist_c=c)
                t = prof.median_time(
                    shortlist.score_batch_sparse,
                    lambda: (cfg_c, warm, serve), repeats=repeats)
                hlo = prof.hlo_cost(
                    lambda s, x: shortlist.score_batch_sparse(cfg_c, s, x),
                    warm, serve)
                table.add_cell(dkey, _mk_cell(
                    "score", "sparse", kmax, d, c, n_serve, t, hlo,
                    dev))

                t = prof.median_time(
                    inference.predict_batch_sparse,
                    lambda: (cfg_c, warm, serve_in, targets, c),
                    repeats=repeats)
                hlo = prof.hlo_cost(
                    lambda s, x: inference._predict_sparse_jit(
                        cfg_c, inference._factors_jit(cfg_c, s, targets),
                        s.sp, s.active, x, c), warm, serve_in)
                table.add_cell(dkey, _mk_cell(
                    "predict", "sparse", kmax, d, c, n_serve, t, hlo,
                    dev))

        if verbose:
            print(f"calibrated K={kmax} D={d} Cs={tuple(cs)} "
                  f"({len(table.entries[dkey])} cells)")

    if base is not None:
        table = base.merge(table)
    return table


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def to_roofline_records(table: CostTable,
                        dkey: Optional[str] = None) -> List[Dict]:
    """CostTable cells as ``benchmarks/roofline.py`` ``figmn_path``
    records — the measured-vs-predicted roofline view of the table.  Cells
    without an HLO analysis (Pallas bodies) are skipped."""
    recs = []
    for key in ([dkey] if dkey else table.device_keys()):
        for cell in table.entries.get(key, ()):
            if not cell.get("hlo"):
                continue
            h = dict(cell["hlo"])
            h.setdefault("coll_bytes_total", 0.0)
            recs.append({
                "arch": "figmn-path",
                "shape": (f"{cell['kind']}-{cell['path']}"
                          f"_k{cell['k']}_d{cell['d']}"
                          f"_c{cell.get('c', 0)}_n{cell['n']}"),
                "mesh": "1x1", "n_devices": 1, "model_axis": 1,
                "kind": "figmn_path", "op": cell["kind"],
                "path": cell["path"], "k": cell["k"], "d": cell["d"],
                "c": cell.get("c", 0), "points": cell["n"],
                "hlo": h, "memory": {}, "device_key": key,
                "measured_s": cell["measured_s"]})
    return recs


def explain(cfg: FIGMNConfig, *, requested: str = "auto", chunk: int = 256,
            vmem_budget: Optional[int] = None, device: Optional[str] = None,
            cost_table=None) -> str:
    """Human-readable dispatch report (``launch/serve.py
    --explain-dispatch``): the decision, the heuristic counterfactual, the
    backing table row and its roofline bottleneck term."""
    d = decide(cfg, requested=requested, chunk=chunk,
               vmem_budget=vmem_budget, device=device,
               cost_table=cost_table)
    lines = [
        f"dispatch: path={d.path!r} reason={d.reason!r} "
        f"(K={cfg.kmax} D={cfg.dim} C={cfg.shortlist_c} chunk={chunk})",
        f"  device_key: {d.device_key} (backend={d.backend})",
        f"  vmem_budget: {d.vmem_budget} bytes ({d.vmem_source}) — "
        f"kernel needs {figmn_stream.vmem_bytes(cfg.kmax, cfg.dim, chunk)} "
        f"bytes",
        f"  heuristic counterfactual: {d.heuristic_path!r}"
        + (" (table overrode it)" if d.path != d.heuristic_path else
           " (agrees)"),
    ]
    if d.cell is not None:
        cell = d.cell
        lines.append(
            f"  table row: kind={cell['kind']} path={cell['path']} "
            f"k={cell['k']} d={cell['d']} c={cell.get('c', 0)} "
            f"n={cell['n']} measured={cell['measured_s']:.3e}s")
        if cell.get("predicted_s") is not None:
            ratio = cell["measured_s"] / max(cell["predicted_s"], 1e-30)
            lines.append(
                f"  roofline: predicted={cell['predicted_s']:.3e}s "
                f"(bottleneck={cell.get('bottleneck')}, "
                f"measured/predicted={ratio:.1f}x)")
    if d.candidates:
        ranked = sorted(d.candidates.items(), key=lambda kv: kv[1])
        lines.append("  candidates: " + " | ".join(
            f"{p} {v:.3e} s/pt" for p, v in ranked))
    if d.reason in ("heuristic", "no_table_entry"):
        lines.append("  (no usable table for this device key: decisions "
                     "are the PR-6 heuristic, bit-compatibly — run "
                     "benchmarks.figmn_dispatch to calibrate)")
    return "\n".join(lines)
