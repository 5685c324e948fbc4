"""Pallas TPU kernel: VMEM-resident streaming FIGMN fit.

The FIGMN working set is K·D² precision entries.  When it fits in VMEM the
HBM-streaming formulation (one read + one read/write pass over Λ per point,
~0.4 FLOP/byte) is the wrong shape for a TPU: this kernel copies Λ into a
VMEM scratch once per chunk, runs every point of the chunk against it, and
copies it back once.

    HBM traffic per point:  3·K·D²·4 bytes   →   D·4 bytes

Per point it runs the fused exact-mode step (``figmn.fused_step_coeffs``):
one matvec y_k = Λ_k e_k per component on the MXU, the gate and posterior
over K on (K, 1) columns, then the rank-one update
Λ_k ← (Λ_k − β_k y_k y_kᵀ) / (1 − ω_k) on the VPU, in row blocks.

Layout: D is zero-padded to a multiple of 128 lanes and K to a multiple of
8 sublanes.  The padding is exact: padded coordinates of x, μ and y are 0,
so they add nothing to d² and leave Λ's padded block at 0; padded
components are inactive, so their posterior is 0 and their update is the
identity.  ``vmem_bytes`` counts what the kernel holds in VMEM at those
padded sizes; ``stream.ingest.select_path`` admits the kernel only when
that fits the device's VMEM (``repro.devices``).

Restrictions: the exact update mode (PSD-safe) only, and no creation:
points that fail the chi² gate leave the state untouched and are counted
out of ``n_accepted``; ``stream.runtime`` routes them to the lifecycle
spawn buffer.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE, _SUB = 128, 8
#: Λ rows updated per unrolled block of the rank-one update
_ROWS = 128
_LOG_2PI = 1.8378770664093453


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_bytes(k: int, d: int, n: int) -> int:
    """Upper bound on the VMEM the kernel needs for K components of
    dimension D and an N-point chunk.

    Counts the Λ scratch and the (N, D) chunk exactly, and every (K, D)
    array and (K, 1) column the kernel or its compiled body keeps — the
    operands, the scratch and the temporaries the compiler spills — at 12
    and 24 of each, plus 2 MiB for the compiler's own scratch.  The v5e
    compile used less than this at every size tried.
    """
    kp, dp = _pad(k, _SUB), _pad(d, _LANE)
    return (kp * dp * dp * 4 + _pad(n, _SUB) * dp * 4
            + 12 * kp * dp * 4 + 24 * kp * _LANE * 4 + 2 * 2 ** 20)


def _stream_kernel(thresh_ref, xs_ref, mu0_ref, lam_hbm, logdet0_ref,
                   sp0_ref, active_ref,
                   mu_out, lam_out_hbm, logdet_out, sp_out, nacc_out,
                   lam, diff_s, y_s, beta_s, omw_s,
                   *, n_points: int, dim: int, kp: int, dp: int):
    """One invocation runs the whole chunk.  Λ lives in the ``lam`` VMEM
    scratch; μ, log|C| and sp live in their VMEM outputs; per-component
    scalars are (Kp, 1) columns."""
    pltpu.sync_copy(lam_hbm, lam)
    mu_out[...] = mu0_ref[...]
    logdet_out[...] = logdet0_ref[...]
    sp_out[...] = sp0_ref[...]
    active = active_ref[...] > 0                        # (Kp, 1)
    thresh = thresh_ref[0]
    rows = min(_ROWS, dp)

    def matvec(k, carry):
        y_s[pl.ds(k, 1), :] = jnp.dot(
            diff_s[pl.ds(k, 1), :], lam[k],
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)         # (1, Dp)  MXU
        return carry

    def update(k, carry):
        y_row = y_s[pl.ds(k, 1), :]                     # (1, Dp)
        y_col = jnp.transpose(
            jnp.broadcast_to(y_row, (_SUB, dp)))[:, 0:1]   # (Dp, 1)
        beta = beta_s[pl.ds(k, 1), :]
        one_m_w = omw_s[pl.ds(k, 1), :]
        for r in range(0, dp, rows):
            yy = y_col[r:r + rows] * y_row
            lam[k, r:r + rows, :] = (lam[k, r:r + rows, :]
                                     - beta * yy) / one_m_w
        return carry

    def point(t, nacc):
        x = xs_ref[pl.ds(t, 1), :]                      # (1, Dp)
        mu = mu_out[...]
        diff = x - mu                                   # (Kp, Dp)
        diff_s[...] = diff
        jax.lax.fori_loop(0, kp, matvec, 0)
        y = y_s[...]
        d2 = jnp.sum(diff * y, axis=1, keepdims=True)   # (Kp, 1)
        hit = jnp.where(active & (d2 < thresh), 1, 0)
        accept = jnp.max(hit, axis=0, keepdims=True) > 0   # (1, 1)

        logp = -0.5 * (dim * _LOG_2PI + logdet_out[...] + d2)
        logw = jnp.where(active, logp + jnp.log(
            jnp.maximum(sp_out[...], 1e-30)), -1e30)
        m = jnp.max(logw, axis=0, keepdims=True)
        p_un = jnp.where(active, jnp.exp(logw - m), 0.0)
        post = p_un / jnp.maximum(jnp.sum(p_un, axis=0, keepdims=True),
                                  1e-30)
        post = jnp.where(accept, post, 0.0)             # gate off ⇒ no-op

        sp_new = sp_out[...] + post
        w = post / jnp.maximum(sp_new, 1e-30)
        one_m_w = 1.0 - w
        # fused exact-mode coefficients (see core.figmn.fused_step_coeffs)
        beta = w / (1.0 + w * d2)
        dlogdet = dim * jnp.log(one_m_w) + jnp.log1p(w * d2)

        mu_out[...] = mu + w * diff
        logdet_out[...] = logdet_out[...] + dlogdet
        sp_out[...] = sp_new
        # lane-broadcast once here: Mosaic cannot broadcast a (1, 1) value
        # over both sublanes and lanes inside the update
        beta_s[...] = jnp.broadcast_to(beta, (kp, dp))
        omw_s[...] = jnp.broadcast_to(one_m_w, (kp, dp))
        jax.lax.fori_loop(0, kp, update, 0)
        return nacc + accept.astype(jnp.int32)

    nacc_out[...] = jax.lax.fori_loop(
        0, n_points, point, jnp.zeros((1, 1), jnp.int32))
    pltpu.sync_copy(lam, lam_out_hbm)


@functools.partial(jax.jit, static_argnames=("dim", "n_points", "interpret",
                                             "vmem_limit"))
def figmn_stream_pallas(xs, mu0, lam0, logdet0, sp0, active0, thresh, *,
                        dim: int, n_points: int, interpret: bool = False,
                        vmem_limit: Optional[int] = None):
    """Run a chunk with VMEM-resident state.

    xs: (N, D); mu0 (K, D); lam0 (K, D, D); logdet0, sp0, active0 (K,);
    thresh: (1,) chi² gate.  Returns (mu, lam, logdet, sp, n_accepted (1,)).
    All updates use the exact (PSD-safe) mode; points failing the gate are
    no-ops.  vmem_limit: the VMEM the compiler may give the kernel (the
    device's ``repro.devices`` entry; None keeps the compiler's default).
    interpret: run the kernel body on the CPU (tests, CPU hosts).
    """
    k, d = mu0.shape
    kp, dp = _pad(k, _SUB), _pad(d, _LANE)
    f32 = jnp.float32

    def col(a, dtype=f32):
        return jnp.pad(a.astype(dtype), (0, kp - k))[:, None]

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_stream_kernel, n_points=n_points, dim=dim,
                               kp=kp, dp=dp)
    mu, lam, logdet, sp, nacc = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  vmem, vmem, hbm, vmem, vmem, vmem],
        out_specs=[vmem, hbm, vmem, vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct((kp, dp), f32),
                   jax.ShapeDtypeStruct((kp, dp, dp), f32),
                   jax.ShapeDtypeStruct((kp, 1), f32),
                   jax.ShapeDtypeStruct((kp, 1), f32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((kp, dp, dp), f32)]
        + [pltpu.VMEM((kp, dp), f32)] * 4,
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(thresh.astype(f32),
      jnp.pad(xs.astype(f32), ((0, 0), (0, dp - d))),
      jnp.pad(mu0.astype(f32), ((0, kp - k), (0, dp - d))),
      jnp.pad(lam0.astype(f32), ((0, kp - k), (0, dp - d), (0, dp - d))),
      col(logdet0), col(sp0), col(active0, jnp.int32))
    return mu[:k, :d], lam[:k, :d, :d], logdet[:k, 0], sp[:k, 0], nacc[0]
