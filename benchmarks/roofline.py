"""§Roofline: three-term roofline per (arch × shape × mesh) from the dry-run
artifacts.

   compute    = HLO_FLOPs/device            / peak FLOP/s   (bf16 MXU)
   memory     = HLO_traffic_bytes/device    / peak HBM B/s
   collective = collective_bytes/device     / 50e9   B/s    (ICI per link)

with the peaks of the record's device kind from ``repro.devices`` (dry-run
records target a TPU v5e pod).

(The dry-run analyses the per-device partitioned module, so terms are
per-chip seconds directly.)  The dominant term is the bottleneck; the
roofline fraction reported in EXPERIMENTS.md §Perf is

   fraction = useful_time / dominant_term,
   useful_time = MODEL_FLOPS/device / 197e12,
   MODEL_FLOPS = 6·N_active·tokens (train) or 2·N_active·tokens (fwd only)

i.e. an MFU-style measure of how much of the machine's bound resource the
step spends on model mathematics.  The HBM-traffic proxy counts fusion
boundaries (see hlo_analysis.py) and tends to over-estimate by ~2× vs an
ideally-pipelined TPU — uniform across cells, so dominance classification
and before/after deltas are meaningful; absolute memory fractions are
conservative.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro import devices

#: the device kind dry-run records (which carry none) are analysed for
DRYRUN_KIND = "TPU v5 lite"
ICI_BW = 50e9

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "artifacts",
                            "dryrun")


def figmn_model_flops(k: int, d: int, c: int, points: int,
                      op: str = "ingest") -> float:
    """The paper cost model as FLOPs, per dispatch path.

    Dense ingest (eqs. 3–10/20–26): 2 passes over K·D² per point — the
    Mahalanobis distance pass and the rank-one precision update — at
    2 FLOPs per MAC ⇒ 4·K·D².  Shortlisted (PR 4): the exact D² work runs
    on C gathered rows plus an O(K·D) bound pass ⇒ 4·C·D² + 2·K·D.  Reads
    (score / eq. 27 predict) run the distance pass only: half the ingest
    passes.
    """
    passes = 4.0 if op == "ingest" else 2.0
    if c and c > 0:
        per_pt = passes * c * d * d + 2.0 * k * d
    else:
        per_pt = passes * k * d * d
    return per_pt * points


def _figmn_kd_from_shape(rec: Dict) -> Dict:
    """Legacy figmn_fit dry-run records carry (K, D) only in the
    "d{dim}_k{kmax}" shape string; newer writers stamp explicit fields."""
    import re
    m = re.match(r"d(\d+)_k(\d+)", rec.get("shape", ""))
    if m:
        return {"d": int(m.group(1)), "k": int(m.group(2))}
    return {}


def model_flops_per_device(rec: Dict) -> float:
    n = rec.get("n_active_params", rec.get("n_params", 0))
    kind = rec.get("kind", "train")
    if kind == "train":
        tokens = rec["seq_len"] * rec["global_batch"]
        total = 6.0 * n * tokens
    elif kind == "prefill":
        tokens = rec["seq_len"] * rec["global_batch"]
        total = 2.0 * n * tokens
    elif kind in ("figmn_fit", "figmn_path"):
        # paper cost model from the record's actual (K, D, C) fields —
        # not from an axis-count guess.  The component pool is sharded
        # over the mesh's "model" axis (launch/dryrun.lower_figmn), so
        # per-device K divides by that axis size, not by n_devices//2.
        kd = {**_figmn_kd_from_shape(rec), **{f: rec[f]
              for f in ("k", "d", "c") if f in rec}}
        points = rec.get("points", rec.get("seq_len", 1))
        if "k" in kd and "d" in kd:
            total = figmn_model_flops(kd["k"], kd["d"], kd.get("c", 0),
                                      points, rec.get("op", "ingest"))
        else:   # no shape info at all: K·D² ≈ n_params, dense ingest
            total = 4.0 * n * points
        return total / max(int(rec.get("model_axis", 1)), 1)
    else:                                              # decode: 1 token/seq
        total = 2.0 * n * rec["global_batch"]
    return total / rec["n_devices"]


def analyze_record(rec: Dict) -> Optional[Dict]:
    if "skipped" in rec or "hlo" not in rec:
        return None
    h = rec["hlo"]
    # calibration records (costmodel.to_roofline_records) name the device
    # they were measured on in their "<device_kind>|jax-<version>" key
    kind = rec["device_key"].split("|")[0] if "device_key" in rec \
        else DRYRUN_KIND
    peaks = devices.lookup(kind)
    peak_flops, hbm_bw = peaks.flops, peaks.hbm_bw
    terms = {
        "compute_s": h["flops"] / peak_flops,
        "memory_s": h["traffic_bytes"] / hbm_bw,
        "collective_s": h["coll_bytes_total"] / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    useful = model_flops_per_device(rec) / peak_flops
    frac = useful / max(terms[dominant], 1e-30)
    row = {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        **{k: v for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "useful_s": useful,
        "roofline_fraction": frac,
        "model_vs_hlo_flops": model_flops_per_device(rec)
        / max(h["flops"], 1e-30),
        "mem_gib_per_dev": rec.get("memory", {})
        .get("argument_size_in_bytes", 0) / 2**30,
        "temp_gib_per_dev": rec.get("memory", {})
        .get("temp_size_in_bytes", 0) / 2**30,
    }
    if rec.get("kind") == "figmn_path":
        row["measured_s"] = rec.get("measured_s")
        row["path"] = rec.get("path")
        row["op"] = rec.get("op")
    return row


def load_all(art_dir: str = ARTIFACT_DIR) -> List[Dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        row = analyze_record(rec)
        if row:
            rows.append(row)
    return rows


def markdown_table(rows: List[Dict], mesh: str = "16x16") -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | "
           "dominant | useful s | fraction | model/HLO flops | args GiB |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        if r["mesh"] != mesh:
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} | "
            f"{r['memory_s']:.2e} | {r['collective_s']:.2e} | "
            f"**{r['dominant']}** | {r['useful_s']:.2e} | "
            f"{r['roofline_fraction']:.3f} | "
            f"{r['model_vs_hlo_flops']:.2f} | {r['mem_gib_per_dev']:.2f} |")
    return hdr + "\n".join(lines)


def pick_hillclimb_cells(rows: List[Dict]) -> Dict[str, Dict]:
    pod1 = [r for r in rows if r["mesh"] == "16x16"
            and r["arch"] != "figmn-core"]
    worst = min(pod1, key=lambda r: r["roofline_fraction"])
    coll = max(pod1, key=lambda r: r["collective_s"]
               / max(r["compute_s"] + r["memory_s"], 1e-30))
    figmn = next((r for r in rows if r["arch"] == "figmn-core"
                  and r["mesh"] == "16x16"), None)
    return {"worst_fraction": worst, "most_collective_bound": coll,
            "paper_representative": figmn}


def main(smoke: bool = False):
    # no size knob: analyses whatever dry-run artifacts exist (none in CI
    # smoke ⇒ exercises the load/parse path and prints nothing)
    del smoke
    rows = load_all()
    for r in rows:
        if r["mesh"] == "16x16":
            print(f"roofline/{r['arch']}__{r['shape']},0,"
                  f"dom={r['dominant']};frac={r['roofline_fraction']:.3f};"
                  f"c={r['compute_s']:.2e};m={r['memory_s']:.2e};"
                  f"x={r['collective_s']:.2e}")
        elif r["arch"] == "figmn-path":
            # dispatch calibration cells (benchmarks.figmn_dispatch):
            # measured vs HLO-predicted seconds per path
            pred = max(r["compute_s"], r["memory_s"])
            meas = r.get("measured_s")
            mvp = (f"{meas / max(pred, 1e-30):.1f}x"
                   if meas is not None else "n/a")
            print(f"roofline/{r['arch']}__{r['shape']},0,"
                  f"dom={r['dominant']};pred={pred:.2e};"
                  f"meas={meas if meas is None else format(meas, '.2e')};"
                  f"meas/pred={mvp}")
    if not any(r["mesh"] == "16x16" and r["arch"] != "figmn-core"
               for r in rows):
        print("roofline/no_dryrun_artifacts,0,run repro.launch.dryrun "
              "--all first")
        return
    picks = pick_hillclimb_cells(rows)
    for tag, r in picks.items():
        if r:
            print(f"roofline/pick_{tag},0,{r['arch']}__{r['shape']}")


if __name__ == "__main__":
    main()
