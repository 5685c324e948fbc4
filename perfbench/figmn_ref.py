"""Plain reference of the precision-form IGMN (Pinto & Engel 2015, §3).

Written from the paper, in ``jax.numpy``, with no import from the
program: the exact-moment learning step (``update_mode="exact"``), the
mixture log-density and the eq. 27 conditional mean.  The benchmark runs
it in float32 with matmuls at `highest` to decide whether the program's
answers are correct, and in bfloat16 as the control that such a check
has to fail.

A pool is a dict of arrays: ``mu`` (K, D), ``lam`` (K, D, D) = C⁻¹,
``logdet`` (K,) = log|C|, ``sp`` (K,), ``v`` (K,), ``active`` (K,) bool.

Learning step for a point x (Algorithm 1 with the exact recursion
C' = (1-ω)C + ω(1-ω)eeᵀ):

* e_k = x − μ_k, y_k = Λ_k e_k, d²_k = e_kᵀ y_k;
* x is novel when no active component has d² below the χ²_{D,1−β}
  quantile: it takes the first free slot, or the active slot with the
  least sp when none is free, with μ = x, Λ = σ_ini⁻² I, sp = v = 1;
* otherwise every active component updates with its posterior p(k|x) ∝
  N(x; μ_k, C_k) sp_k: v += 1, sp += p, ω = p / sp, μ += ω e,
  Λ ← (Λ − ω/(1 + ω d²) y yᵀ) / (1 − ω),
  log|C| += D log(1 − ω) + log(1 + ω d²).
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import ndtri

LOG_2PI = 1.8378770664093453
HIGHEST = jax.lax.Precision.HIGHEST

Pool = Dict[str, jax.Array]


def novelty_threshold(dim: int, beta: float) -> float:
    """χ²_{D,1−β} by the Wilson–Hilferty approximation, the gate the
    configuration states (``FIGMNConfig.beta``)."""
    z = float(ndtri(1.0 - beta))
    k = float(dim)
    return k * (1.0 - 2.0 / (9.0 * k) + z * np.sqrt(2.0 / (9.0 * k))) ** 3


def cast(pool: Pool, dtype) -> Pool:
    return {k: (v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                else v) for k, v in pool.items()}


def _learn_one(pool: Pool, x, inv_var, logdet0, thresh) -> Pool:
    mu, lam, logdet = pool["mu"], pool["lam"], pool["logdet"]
    sp, v, active = pool["sp"], pool["v"], pool["active"]
    k, d = mu.shape
    dt = mu.dtype
    e = x[None, :] - mu
    y = jnp.sum(lam * e[:, None, :], axis=-1)
    d2 = jnp.sum(e * y, axis=-1)
    accept = jnp.any(active & (d2 < thresh))
    logp = -0.5 * (d * LOG_2PI + logdet + d2)
    logw = jnp.where(active, logp + jnp.log(jnp.where(active, sp, 1)),
                     -jnp.inf)
    logw = jnp.where(jnp.any(active), logw, 0)
    post = jnp.where(active & accept, jax.nn.softmax(logw), 0).astype(dt)
    sp_u = sp + post
    w = post / jnp.where(sp_u > 0, sp_u, 1)
    c = w / (1 + w * d2)
    lam_u = (lam - c[:, None, None] * y[:, :, None] * y[:, None, :]) \
        / (1 - w)[:, None, None]
    free = ~active
    slot = jnp.where(jnp.any(free), jnp.argmax(free),
                     jnp.argmin(jnp.where(active, sp, jnp.inf)))
    make = (~accept) & (jnp.arange(k) == slot)
    eye = jnp.eye(d, dtype=dt) * inv_var[None, :]
    return {
        "mu": jnp.where(make[:, None], x[None, :], mu + w[:, None] * e),
        "lam": jnp.where(make[:, None, None], eye[None], lam_u),
        "logdet": jnp.where(make, logdet0,
                            logdet + d * jnp.log1p(-w) + jnp.log1p(w * d2)),
        "sp": jnp.where(make, 1, sp_u).astype(dt),
        "v": jnp.where(make, 1, v + (active & accept)).astype(dt),
        "active": active | make,
    }


@partial(jax.jit, donate_argnums=(0,))
def learn(pool: Pool, xs, sigma, thresh) -> Pool:
    """The pool after the points ``xs`` (N, D), one after another; the
    dtype of the pool is the dtype of the arithmetic."""
    dt = pool["mu"].dtype
    sigma = sigma.astype(jnp.float32)
    inv_var = (1.0 / (sigma * sigma)).astype(dt)
    logdet0 = jnp.sum(2.0 * jnp.log(sigma)).astype(dt)

    def step(p, x):
        return _learn_one(p, x, inv_var, logdet0, thresh), None

    with jax.default_matmul_precision("highest"):
        pool, _ = jax.lax.scan(step, pool, xs.astype(dt))
    return pool


def _blocks(a, kb):
    return a.reshape((a.shape[0] // kb, kb) + a.shape[1:])


def _block_size(k: int, kb: int) -> int:
    """The largest divisor of the pool size ``k`` not above ``kb``."""
    return max(b for b in range(1, min(kb, k) + 1) if k % b == 0)


@partial(jax.jit, static_argnames=("kb",))
def log_density(pool: Pool, xs, kb: int = 64):
    """(B,) log Σ_k N(x; μ_k, C_k) sp_k / Σ sp over the active slots."""
    dt = pool["mu"].dtype
    xs = xs.astype(dt)
    d = xs.shape[1]

    def block(args):
        mu, lam, logdet = args
        diff = xs[:, None, :] - mu[None]
        y = jnp.einsum("bkd,kde->bke", diff, lam, precision=HIGHEST)
        d2 = jnp.sum(diff * y, axis=-1)
        return -0.5 * (d * LOG_2PI + logdet[None] + d2)

    kb = _block_size(pool["mu"].shape[0], kb)
    logp = jax.lax.map(block, (_blocks(pool["mu"], kb),
                               _blocks(pool["lam"], kb),
                               _blocks(pool["logdet"], kb)))
    logp = jnp.moveaxis(logp, 1, 0).reshape(xs.shape[0], -1)
    active = pool["active"]
    sp = jnp.where(active, pool["sp"], 0)
    logprior = jnp.log(jnp.where(active, sp, 1)) - jnp.log(jnp.sum(sp))
    logj = jnp.where(active[None], logp + logprior[None], -jnp.inf)
    return jax.nn.logsumexp(logj.astype(jnp.float32), axis=1)


@partial(jax.jit, static_argnames=("n_out", "kb"))
def conditional_mean(pool: Pool, xs_in, n_out: int, kb: int = 64):
    """(B, n_out) eq. 27: the last ``n_out`` dimensions given the rest.

    Per component, with Λ = [[X, Y], [Yᵀ, W]] split into known and target
    blocks: x̂_k = μ_t − W⁻¹Yᵀ(x − μ_i); the known block's marginal has
    precision X − Y W⁻¹ Yᵀ and log-determinant log|C| + log|W|; the
    answer is the posterior-weighted mean of the x̂_k.  The o×o solve and
    determinant run in float32 whatever the pool's dtype."""
    dt = pool["mu"].dtype
    xs_in = xs_in.astype(dt)
    ni = xs_in.shape[1]

    def block(args):
        mu, lam, logdet = args
        X, Y, W = lam[:, :ni, :ni], lam[:, :ni, ni:], lam[:, ni:, ni:]
        W32 = W.astype(jnp.float32)
        winv_z = jnp.linalg.solve(
            W32, jnp.swapaxes(Y, 1, 2).astype(jnp.float32)).astype(dt)
        S = X - jnp.einsum("kio,koj->kij", Y, winv_z, precision=HIGHEST)
        logdet_in = logdet + jnp.linalg.slogdet(W32)[1].astype(dt)
        diff = xs_in[:, None, :] - mu[None, :, :ni]
        xhat = mu[None, :, ni:] - jnp.einsum("koi,bki->bko", winv_z, diff,
                                             precision=HIGHEST)
        t = jnp.einsum("bki,kij->bkj", diff, S, precision=HIGHEST)
        d2 = jnp.sum(diff * t, axis=-1)
        return -0.5 * (ni * LOG_2PI + logdet_in[None] + d2), xhat

    kb = _block_size(pool["mu"].shape[0], kb)
    logp, xhat = jax.lax.map(block, (_blocks(pool["mu"], kb),
                                     _blocks(pool["lam"], kb),
                                     _blocks(pool["logdet"], kb)))
    b = xs_in.shape[0]
    logp = jnp.moveaxis(logp, 1, 0).reshape(b, -1)
    xhat = jnp.moveaxis(xhat, 1, 0).reshape(b, -1, n_out)
    active = pool["active"]
    logw = jnp.where(active[None],
                     logp + jnp.log(jnp.where(active, pool["sp"], 1))[None],
                     -jnp.inf).astype(jnp.float32)
    post = jax.nn.softmax(logw, axis=1)
    return jnp.einsum("bk,bko->bo", post, xhat.astype(jnp.float32),
                      precision=HIGHEST)
