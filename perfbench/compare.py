"""The numbers that decide ``correct``: what the timed path produced
against the plain reference.

Learned pools (the ingest cells).  Two sound float32 replays of the same
points part ways, now and then, at a discrete choice that rounding
decides: at each split seen, one side had just made a component in the
slot (sp about 1) where the other kept one that held several points, as
when a novel point recycles one of several slots whose sp agree to the
last bits.  From then on the two pools hold different components in
those slots, and later posteriors move a few more (PERF.md gives the look: from the same state, the program
and the reference replay the chunk to 6e-5 of each other and 7e-4 of a
float64 replay).  So no number compares one slot's Λ across such a split;
the numbers are steady ones, which hold across it, and one count, which a
split moves by a few slots but a fault in a share of the slots moves by
that share:

* ``mass_gap``: |Σ sp − Σ sp^ref| over the points ingested (Σ sp grows
  by exactly one per point);
* ``moment_rel``: ‖Σ sp·μ − Σ sp^ref·μ^ref‖ over ‖Σ sp^ref·μ^ref −
  Σ sp^start·μ^start‖ (sp·μ grows by p·x per point, so Σ sp·μ by x);
* ``lam_rel_median``: the median, over the components to which the
  reference gave a thousandth of a point or more, of
  ‖Λ_k − Λ_k^ref‖_F / ‖Λ_k^ref‖_F;
* ``lam_far_share``: the share of active slots whose relative Λ gap is
  above ``FAR``;
* ``active_diff``: slots active on one side only (limit 0).

Answers (the read cell): ``score_gap`` is the largest |Δ log p| in nats
over the sampled density answers, ``label_gap`` the largest |Δ| over the
sampled label answers' values.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


#: the relative Λ_k gap above which ``lam_far_share`` counts a slot
FAR = 1e-2


def _lam_rel(prog, ref):
    f32 = jnp.float32
    dl = jnp.sqrt(jnp.sum(jnp.square(prog["lam"].astype(f32)
                                     - ref["lam"].astype(f32)), axis=(1, 2)))
    nl = jnp.sqrt(jnp.sum(jnp.square(ref["lam"].astype(f32)), axis=(1, 2)))
    return jnp.where(ref["active"], dl / nl, 0)


#: (K,) ‖Λ_k − Λ_k^ref‖_F / ‖Λ_k^ref‖_F, 0 where the reference's slot is
#: free
lam_rel = jax.jit(_lam_rel)


@jax.jit
def _pool_gaps(prog, ref, mu0, sp0):
    f32 = jnp.float32
    act = ref["active"]
    sp_p = jnp.where(prog["active"], prog["sp"].astype(f32), 0)
    sp_r = jnp.where(act, ref["sp"].astype(f32), 0)
    m_p = jnp.sum(sp_p[:, None] * prog["mu"].astype(f32), axis=0)
    m_r = jnp.sum(sp_r[:, None] * ref["mu"].astype(f32), axis=0)
    m_0 = jnp.sum(sp0[:, None] * mu0, axis=0)
    moved = act & (sp_r - sp0 >= 1e-3)
    rel = _lam_rel(prog, ref)
    return {"mass": jnp.abs(jnp.sum(sp_p) - jnp.sum(sp_r)),
            "moment_rel": jnp.linalg.norm(m_p - m_r)
            / jnp.maximum(jnp.linalg.norm(m_r - m_0), 1e-30),
            "lam_rel_median": jnp.nanmedian(jnp.where(moved, rel, jnp.nan)),
            "lam_far_share": jnp.sum(rel > FAR) / jnp.sum(act),
            "active_diff": jnp.sum(prog["active"] != ref["active"])}


def pool_gaps(prog, ref, start_mu, points: int) -> Dict[str, float]:
    """``start_mu``: the starting pool's means (every slot active with
    sp = 1); ``points``: how many points both sides ingested."""
    k = start_mu.shape[0]
    out = _pool_gaps(prog, ref, jnp.asarray(start_mu, jnp.float32),
                     jnp.ones((k,), jnp.float32))
    gaps = {key: float(v) for key, v in out.items()}
    gaps["mass_gap"] = gaps.pop("mass") / points
    return gaps


def answer_gaps(score: np.ndarray, score_ref: np.ndarray,
                label: np.ndarray, label_ref: np.ndarray) -> Dict[str, float]:
    gaps = {}
    if score.size:
        gaps["score_gap"] = float(np.max(np.abs(
            score.astype(np.float64) - score_ref)))
    if label.size:
        gaps["label_gap"] = float(np.max(np.abs(
            label.astype(np.float64) - label_ref)))
    return gaps


def judge(gaps: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Tuple[float, float]]]:
    """(every gap within its limit, {name: (value, limit)}).  A gap with
    no limit, or a limit with no gap, is a failure: nothing goes
    unchecked by accident."""
    checks = {k: (gaps.get(k, float("nan")), limits.get(k, float("nan")))
              for k in sorted(set(gaps) | set(limits))}
    ok = all(v <= lim for v, lim in checks.values())
    return ok, checks
