"""The pools a cell starts from, built on the device in one jitted call
from the run's seed; only the kmax means and σ_ini come from the host.
The same call rebuilds a pool for the reference after the window, bit
for bit.

``full_pool``: the ingest cells' start, a full pool as a long-running
stream holds one.  ``kmax`` creations (Algorithm 3) leave every slot
active with μ at a stream point, Λ = σ_ini⁻² I, log|C| = Σ log σ_ini²,
sp = v = 1.

``served_pool``: the read cell's published snapshot, shaped as a learned
one.  Each Λ_k = S(I + G_k G_kᵀ)S with S = diag(σ_ini⁻¹) and a seeded
D × r factor G_k whose feature rows are N(0, a²/F) and label rows
N(0, b²), so every component couples features and labels (eq. 27's
W⁻¹Z term is not zero) and W is not diagonal; the label block of μ_k is
its point's one-hot mixed with a seeded soft label, and sp = v are
seeded in [1, sp_max].  log|C| comes from the determinant lemma.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import streams


@partial(jax.jit, static_argnames=("dtype",))
def _build(mu, sigma, dtype: str = "float32"):
    k, d = mu.shape
    dt = jnp.dtype(dtype)
    inv_var = 1.0 / (sigma * sigma)
    return {
        "mu": mu.astype(dt),
        "lam": jnp.broadcast_to(jnp.diag(inv_var).astype(dt), (k, d, d)),
        "logdet": jnp.full((k,), jnp.sum(2.0 * jnp.log(sigma)), dt),
        "sp": jnp.ones((k,), dt),
        "v": jnp.ones((k,), dt),
        "active": jnp.ones((k,), bool),
    }


def means(model: streams.ClassModel, seed: int, kmax: int) -> np.ndarray:
    return streams.draw(model, seed, kmax, streams.POOL)


def full_pool(model: streams.ClassModel, seed: int, kmax: int,
              sigma: np.ndarray, dtype: str = "float32"):
    """The ingest cells' starting pool as a dict of device arrays."""
    return _build(jnp.asarray(means(model, seed, kmax)),
                  jnp.asarray(sigma), dtype=dtype)


@partial(jax.jit, static_argnames=("classes", "rank", "dtype"))
def _build_served(mu, sigma, key, classes: int, rank: int, a, b, mix,
                  sp_max, dtype: str = "float32"):
    k, d = mu.shape
    f = d - classes
    dt = jnp.dtype(dtype)
    kg, kl, ks = jax.random.split(key, 3)
    g = jax.random.normal(kg, (k, d, rank), jnp.float32)
    g = g * jnp.concatenate([jnp.full((f,), a / np.sqrt(f)),
                             jnp.full((classes,), b)])[None, :, None]
    inv_sd = 1.0 / sigma
    lam = (inv_sd[None, :, None] * inv_sd[None, None, :]
           * (jnp.eye(d)[None]
              + jnp.einsum("kir,kjr->kij", g, g,
                           precision=jax.lax.Precision.HIGHEST)))
    inner = jnp.eye(rank)[None] + jnp.einsum(
        "kir,kis->krs", g, g, precision=jax.lax.Precision.HIGHEST)
    logdet = (jnp.sum(2.0 * jnp.log(sigma))
              - jnp.linalg.slogdet(inner)[1])
    soft = jax.nn.softmax(jax.random.normal(kl, (k, classes)), axis=1)
    mu = mu.at[:, f:].set((1 - mix) * mu[:, f:] + mix * soft)
    sp = jax.random.uniform(ks, (k,), jnp.float32, 1.0, sp_max)
    return {"mu": mu.astype(dt), "lam": lam.astype(dt),
            "logdet": logdet.astype(dt), "sp": sp.astype(dt),
            "v": sp.astype(dt), "active": jnp.ones((k,), bool)}


def served_pool(model: streams.ClassModel, seed: int, kmax: int,
                sigma: np.ndarray, shape: Dict[str, Any],
                dtype: str = "float32"):
    """The read cell's snapshot; ``shape`` is the traffic file's
    ``snapshot``: ``rank``, ``feature_scale`` (a), ``label_scale`` (b),
    ``label_mix`` and ``sp_max``."""
    key = int(streams.seeded(seed, streams.SNAPSHOT).integers(0, 2 ** 31))
    return _build_served(
        jnp.asarray(means(model, seed, kmax)), jnp.asarray(sigma),
        jax.random.PRNGKey(key), classes=model.classes,
        rank=int(shape["rank"]), a=float(shape["feature_scale"]),
        b=float(shape["label_scale"]), mix=float(shape["label_mix"]),
        sp_max=float(shape["sp_max"]), dtype=dtype)
