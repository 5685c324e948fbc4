"""jit'd public wrappers around the Pallas kernels.

Handles: padding D up to MXU-aligned tiles (zero-padding is exact for all
three ops — padded rows/cols contribute 0 to quadratic forms and matvecs and
are sliced off afterwards), tile-size selection under a VMEM budget, and
interpret mode on the CPU platform (``interpret=True`` executes the kernel
body on the host for correctness validation).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import figmn_sparse, figmn_update, mahalanobis

_LANE = 128
_VMEM_BUDGET = 4 * 1024 * 1024  # conservative per-operand bytes


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def _pad_dim(d: int) -> int:
    return max(_LANE, -(-d // _LANE) * _LANE)


def _pick_block(dpad: int) -> int:
    """Largest 128-multiple tile that divides dpad within the VMEM budget."""
    best = _LANE
    b = _LANE
    while b <= dpad:
        if dpad % b == 0 and b * dpad * 4 <= _VMEM_BUDGET:
            best = b
        b += _LANE
    return best


def _pad_kd(x: jax.Array, dpad: int) -> jax.Array:
    k, d = x.shape
    return jnp.pad(x, ((0, 0), (0, dpad - d)))


def _pad_kdd(x: jax.Array, dpad: int) -> jax.Array:
    k, d, _ = x.shape
    return jnp.pad(x, ((0, 0), (0, dpad - d), (0, dpad - d)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def mahalanobis_sq(diff: jax.Array, lam: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """(K,D),(K,D,D) → (K,) squared Mahalanobis distances (Pallas)."""
    if interpret is None:
        interpret = _interpret_default()
    k, d = diff.shape
    dpad = _pad_dim(d)
    bd = _pick_block(dpad)
    out = mahalanobis.mahalanobis_pallas(
        _pad_kd(diff.astype(jnp.float32), dpad),
        _pad_kdd(lam.astype(jnp.float32), dpad),
        block_d=bd, interpret=interpret)
    return out.astype(diff.dtype)


@functools.partial(jax.jit, static_argnames=("dim", "interpret"))
def precision_rank2_update(lam: jax.Array, logdet: jax.Array,
                           e_star: jax.Array, dmu: jax.Array, w: jax.Array,
                           dim: int,
                           interpret: bool | None = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in Pallas replacement for core.figmn.precision_rank2_update.

    Same math (eqs. 20–21 / 25–26) restructured into two single-pass kernels
    plus O(KD) jnp scalar work — see figmn_update.py module docstring.
    """
    if interpret is None:
        interpret = _interpret_default()
    k, d = e_star.shape
    in_dtype = lam.dtype
    dpad = _pad_dim(d)
    bd = _pick_block(dpad)
    lam_p = _pad_kdd(lam.astype(jnp.float32), dpad)
    e_p = _pad_kd(e_star.astype(jnp.float32), dpad)
    m_p = _pad_kd(dmu.astype(jnp.float32), dpad)
    w32 = w.astype(jnp.float32)

    y, z = figmn_update.matvec2_pallas(lam_p, e_p, m_p, block_d=bd,
                                       interpret=interpret)
    one_m_w = 1.0 - w32
    s = jnp.einsum("kd,kd->k", e_p, y)
    denom1 = 1.0 + w32 * s / one_m_w
    c1 = w32 / (one_m_w * one_m_w * denom1)
    u = jnp.einsum("kd,kd->k", y, m_p)                    # yᵀΔμ
    yb = z / one_m_w[:, None] - (c1 * u)[:, None] * y     # Λ̄Δμ w/o Λ̄
    t = jnp.einsum("kd,kd->k", m_p, z) / one_m_w - c1 * u * u
    c2 = 1.0 / (1.0 - t)

    lam_new = figmn_update.rank2_apply_pallas(
        lam_p, y, yb, 1.0 / one_m_w, c1, c2,
        block_r=bd, block_c=bd, interpret=interpret)[:, :d, :d]

    logdet_new = logdet + dim * jnp.log(one_m_w).astype(logdet.dtype) \
        + jnp.log(jnp.abs(denom1)).astype(logdet.dtype) \
        + jnp.log(jnp.abs(1.0 - t)).astype(logdet.dtype)
    return lam_new.astype(in_dtype), logdet_new


@functools.partial(jax.jit, static_argnames=("dim", "interpret"))
def precision_rank1_update_exact(lam: jax.Array, logdet: jax.Array,
                                 e: jax.Array, w: jax.Array,
                                 dim: int,
                                 interpret: bool | None = None
                                 ) -> Tuple[jax.Array, jax.Array]:
    """Pallas path for the beyond-paper exact single-rank-one update."""
    if interpret is None:
        interpret = _interpret_default()
    k, d = e.shape
    in_dtype = lam.dtype
    dpad = _pad_dim(d)
    bd = _pick_block(dpad)
    lam_p = _pad_kdd(lam.astype(jnp.float32), dpad)
    e_p = _pad_kd(e.astype(jnp.float32), dpad)
    w32 = w.astype(jnp.float32)

    y, _ = figmn_update.matvec2_pallas(lam_p, e_p, e_p, block_d=bd,
                                       interpret=interpret)
    one_m_w = 1.0 - w32
    s = jnp.einsum("kd,kd->k", e_p, y)
    denom = 1.0 + w32 * s
    coef = w32 / denom
    zeros = jnp.zeros_like(y)
    lam_new = figmn_update.rank2_apply_pallas(
        lam_p, y, zeros, 1.0 / one_m_w, coef / one_m_w, jnp.zeros_like(coef),
        block_r=bd, block_c=bd, interpret=interpret)[:, :d, :d]
    logdet_new = logdet + dim * jnp.log(one_m_w).astype(logdet.dtype) \
        + jnp.log1p(w32 * s).astype(logdet.dtype)
    return lam_new.astype(in_dtype), logdet_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def matvec(lam: jax.Array, diff: jax.Array,
           interpret: bool | None = None) -> jax.Array:
    """y = Λ·diff for all K slots (shared distance/update pass)."""
    if interpret is None:
        interpret = _interpret_default()
    k, d = diff.shape
    dpad = _pad_dim(d)
    bd = _pick_block(dpad)
    y, _ = figmn_update.matvec2_pallas(
        _pad_kdd(lam.astype(jnp.float32), dpad),
        _pad_kd(diff.astype(jnp.float32), dpad),
        _pad_kd(jnp.zeros_like(diff, jnp.float32), dpad),
        block_d=bd, interpret=interpret)
    return y[:, :d].astype(diff.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gathered_matvec(lam: jax.Array, diff_sel: jax.Array, idx: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    """y_i = Λ[idx_i]·diff_i for the C shortlisted rows (scalar-prefetch
    gather — reads C·D², not K·D², of Λ).

    Shortlist-path note: padding D up to the 128-lane tile would copy the
    whole (K, D, D) tensor and defeat the gather, so this wrapper requires
    lane-aligned D on TPU (keep Λ padded at rest) and runs unpadded in
    interpret mode, where no tiling constraint applies.
    """
    if interpret is None:
        interpret = _interpret_default()
    c, d = diff_sel.shape
    if not interpret and d % _LANE:
        raise ValueError(
            f"gathered_matvec on TPU needs lane-aligned D (got {d}); keep "
            f"Λ padded at rest instead of per-call padding")
    y = figmn_sparse.gathered_matvec_pallas(
        lam.astype(jnp.float32), diff_sel.astype(jnp.float32),
        idx.astype(jnp.int32), interpret=interpret)
    return y.astype(diff_sel.dtype)


@functools.partial(jax.jit, static_argnames=("dim", "update_mode",
                                             "interpret"))
def scatter_fused_apply(lam: jax.Array, logdet: jax.Array, idx: jax.Array,
                        y_sel: jax.Array, d2_sel: jax.Array,
                        w_sel: jax.Array, dim: int,
                        update_mode: str = "paper",
                        interpret: bool | None = None):
    """Shortlisted fused update: rows idx of Λ get the rank-one apply from
    the shared matvec y (core.figmn.fused_step_coeffs); the K−C untouched
    rows alias the input buffer bit-identically.  logdet is scatter-added
    in O(C) jnp.  Returns (Λ', logdet')."""
    from repro.core.figmn import fused_step_coeffs
    if interpret is None:
        interpret = _interpret_default()
    c, d = y_sel.shape
    if not interpret and d % _LANE:
        raise ValueError(
            f"scatter_fused_apply on TPU needs lane-aligned D (got {d})")
    in_dtype = lam.dtype
    w32 = w_sel.astype(jnp.float32)
    beta, dlogdet = fused_step_coeffs(d2_sel.astype(jnp.float32), w32,
                                      dim, update_mode)
    inv1mw = 1.0 / (1.0 - w32)
    b = beta * inv1mw if update_mode == "exact" else -beta
    coefs = jnp.stack([inv1mw, b], axis=1).astype(jnp.float32)   # (C, 2)
    lam_new = figmn_sparse.scatter_apply_pallas(
        lam.astype(jnp.float32), y_sel.astype(jnp.float32), coefs,
        idx.astype(jnp.int32), interpret=interpret)
    logdet_new = logdet.at[idx].add(dlogdet.astype(logdet.dtype))
    return lam_new.astype(in_dtype), logdet_new


@functools.partial(jax.jit, static_argnames=("dim", "update_mode",
                                             "interpret"))
def fused_apply(lam: jax.Array, logdet: jax.Array,
                y: jax.Array, d2: jax.Array, w: jax.Array, dim: int,
                update_mode: str = "paper",
                interpret: bool | None = None):
    """Single-pass fused update: Λ' from the shared matvec y (see
    core.figmn.fused_step_coeffs) via the tiled rank2_apply kernel."""
    from repro.core.figmn import fused_step_coeffs
    if interpret is None:
        interpret = _interpret_default()
    k, d = y.shape
    in_dtype = lam.dtype
    dpad = _pad_dim(d)
    bd = _pick_block(dpad)
    w32 = w.astype(jnp.float32)
    beta, dlogdet = fused_step_coeffs(d2.astype(jnp.float32), w32,
                                      dim, update_mode)
    one_m_w = 1.0 - w32
    if update_mode == "exact":
        inv1mw = 1.0 / one_m_w
        c1 = beta / one_m_w
    else:
        inv1mw = 1.0 / one_m_w
        c1 = -beta
    y_p = _pad_kd(y.astype(jnp.float32), dpad)
    lam_new = figmn_update.rank2_apply_pallas(
        _pad_kdd(lam.astype(jnp.float32), dpad), y_p, jnp.zeros_like(y_p),
        inv1mw, c1, jnp.zeros_like(c1),
        block_r=bd, block_c=bd, interpret=interpret)[:, :d, :d]
    return (lam_new.astype(in_dtype),
            logdet + dlogdet.astype(logdet.dtype))
