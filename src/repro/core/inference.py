"""Supervised inference — conditional-mean reconstruction (§2.4 / §3 eq. 27).

The IGMN predicts any subset of the joint vector from any other subset.  Given
known elements x_i (indices ``idx_in``) it reconstructs targets x_t
(``idx_out``) as a posterior-weighted conditional mean.

Fast path (the paper's eq. 27): all quantities are extracted from the
precision matrix Λ via the block decomposition

    Λ = [[X, Y], [Z, W]]   (X: known-known, W: target-target, Z = Yᵀ)

  * conditional mean      x̂_t = μ_t − W⁻¹ Z (x_i − μ_i)
    (the paper writes Y W⁻¹; with the [known, target] block layout the
    correctly-oriented operator is W⁻¹Z = (YW⁻¹)ᵀ by symmetry)
  * marginal precision    C_i⁻¹ = X − Y W⁻¹ Z        (Schur complement)
  * marginal determinant  log|C_i| = log|C| + log|W|
    (from |C| = |C_i| · |Schur| and W = Schur⁻¹)

Only W (o×o, o = #targets ≪ D) is ever inverted ⇒ O(KD²·o + Ko³) per query,
versus the baseline's O(KD³).  For o = 1 (the paper's Weka setting) the
"inversion" is a scalar reciprocal.

Serving shape: the read path is TWO stages.  ``_factors_jit`` computes the
per-component factor bundle (W⁻¹Z, the Schur-complement marginal
precision, the marginal log-determinant, diag(W⁻¹) for conditional
variance) once per (state, targets); the blocked (B, ·) kernels then
consume the bundle for any number of batches.  The split is what makes the
serving-cost amortisation possible: a ``FactorCache`` keyed on
(snapshot-epoch, targets-signature) hands the SAME factor arrays to every
request served from one published snapshot, so the O(D³)-adjacent factor
construction is paid once per publish instead of once per call — and the
uncached path runs the identical two stages, so cached and uncached
results are bit-identical by construction (same arrays into the same
jitted kernel), not by numerical coincidence.
``predict_batch_sparse`` is its shortlisted twin (the PR-4 bound pass run on
the known-block marginal): an O(K·i) diag proxy ranks the slots per point
and the exact O(D²·o) work runs on the C gathered rows —
O(K·D + C·D²·o) per point instead of O(K·D²·o), bit-identical to the dense
kernel when C covers the pool (the shortlist would be the identity
permutation, so the sparse jit short-circuits to the SAME dense block
body — see ``predict_batch_sparse`` for the full exactness contract).

Empty-mixture contract: eq. 27 is undefined over zero active components —
the masked softmax would return an all-zero posterior and the "prediction"
would be a silent zero vector.  Every public entry point here checks
``n_active`` HOST-SIDE and raises instead (the one deliberate device sync
of the read path; jitted internals stay branch-free).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from functools import partial
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import figmn
from repro.core.types import Array, FIGMNConfig, FIGMNState, IGMNState

_LOG_2PI = 1.8378770664093453


def _split_indices(dim: int, idx_out) -> Tuple[np.ndarray, np.ndarray]:
    idx_out = np.asarray(idx_out, np.int32)
    idx_in = np.setdiff1d(np.arange(dim, dtype=np.int32), idx_out)
    return idx_in, idx_out


def _as_targets(idx_out) -> Tuple[int, ...]:
    return tuple(int(i) for i in np.asarray(idx_out).reshape(-1))


def require_nonempty(state) -> None:
    """Host-side guard at the inference API boundary.

    With no active components the masked posterior is all-zero and the
    conditional mean degenerates to a zero vector — silent garbage.  A
    mixture you can query must have been fitted first; fail loudly.
    """
    if int(jax.device_get(state.n_active)) == 0:
        raise ValueError(
            "cannot run inference on an empty mixture: no active "
            "components (the eq. 27 posterior is undefined and would "
            "silently return zeros) — fit data first")


class _CondFactors(NamedTuple):
    """Per-component eq. 27 factors, computed once per (state, targets)."""
    mu_in: Array      # (K, i)
    mu_out: Array     # (K, o)
    winv_z: Array     # (K, o, i)  W⁻¹Z — the conditional-mean operator
    prec_in: Array    # (K, i, i)  C_i⁻¹ = X − Y W⁻¹ Z (Schur complement)
    logdet_in: Array  # (K,)       log|C_i| = log|C| + log|W|
    wdiag_inv: Array  # (K, o)     diag(W⁻¹) — per-component conditional
    #                              variance of the targets (the precision
    #                              form's conditional covariance IS W⁻¹)


def _conditional_factors(state: FIGMNState, idx_in: np.ndarray,
                         idx_out: np.ndarray) -> _CondFactors:
    lam = state.lam
    X = lam[:, idx_in[:, None], idx_in[None, :]]        # (K, i, i)
    Y = lam[:, idx_in[:, None], idx_out[None, :]]       # (K, i, o)
    W = lam[:, idx_out[:, None], idx_out[None, :]]      # (K, o, o)
    Z = jnp.swapaxes(Y, -1, -2)                         # (K, o, i)
    winv_z = jnp.linalg.solve(W, Z)                     # o×o solve only
    prec_in = X - jnp.einsum("kio,koj->kij", Y, winv_z)
    _, logdet_w = jnp.linalg.slogdet(W)                 # o×o
    o = idx_out.shape[0]
    winv = jnp.linalg.solve(W, jnp.broadcast_to(jnp.eye(o, dtype=lam.dtype),
                                                W.shape))
    return _CondFactors(mu_in=state.mu[:, idx_in],
                        mu_out=state.mu[:, idx_out],
                        winv_z=winv_z, prec_in=prec_in,
                        logdet_in=state.logdet + logdet_w,
                        wdiag_inv=jnp.diagonal(winv, axis1=1, axis2=2))


@partial(jax.jit, static_argnames=("idx_out_t",))
def _factors_jit(cfg: FIGMNConfig, state: FIGMNState,
                 idx_out_t: Tuple[int, ...]) -> _CondFactors:
    """THE factor stage both read paths (and the FactorCache) run: one
    jitted pass producing the per-component bundle.  Cached and uncached
    serving call this same function, so their downstream bits cannot
    diverge."""
    idx_in, idx_out = _split_indices(cfg.dim, np.asarray(idx_out_t))
    return _conditional_factors(state, idx_in, idx_out)


def _dense_block(f: _CondFactors, ni: int, sp: Array, active: Array,
                 xb: Array, return_var: bool = False) -> Array:
    """The dense eq. 27 block body — THE one implementation both read
    paths run: the dense kernel maps it over every block, and the sparse
    kernel short-circuits to it whenever C covers the pool (the shortlist
    would be the identity permutation), which is what makes the C ≥ K
    case bit-identical BY CONSTRUCTION rather than by lowering
    coincidence.  The W⁻¹Z·diff contraction is spelled as multiply +
    last-axis reduce (not a dot_general) so the gathered twin reduces
    over the same extents.

    return_var stacks the conditional variance as a second row — law of
    total variance over the posterior mixture: Var = Σ post_k
    (diag(W⁻¹)_k + x̂_k²) − x̂², where diag(W⁻¹) is the k-th component's
    conditional covariance diagonal (already in the factor bundle — the
    one extra Schur term the variance query costs)."""
    diff = xb[:, None, :] - f.mu_in[None, :, :]          # (B, K, i)
    xhat = f.mu_out[None, :, :] \
        - jnp.sum(f.winv_z[None] * diff[:, :, None, :], axis=-1)
    t = jnp.einsum("kij,bkj->bki", f.prec_in, diff)
    d2 = jnp.einsum("bki,bki->bk", diff, t)
    logp = -0.5 * (ni * _LOG_2PI + f.logdet_in[None, :] + d2)
    post = figmn.masked_posteriors(logp, sp, active)
    mean = jnp.einsum("bk,bko->bo", post, xhat)
    if not return_var:
        return mean
    ex2 = jnp.einsum("bk,bko->bo", post,
                     f.wdiag_inv[None, :, :] + xhat * xhat)
    return jnp.stack([mean, jnp.maximum(ex2 - mean * mean, 0.0)], axis=1)


def _map_blocks(block, xs: Array, block_b: int) -> Array:
    """Fixed-shape serving blocking (shared by BOTH eq. 27 read paths).

    XLA's lowering of a big (B, K) contraction is batch-size dependent —
    a 4096-row GEMM and a 512-row one may reassociate reductions
    differently — so large requests are mapped over fixed (block_b, ·)
    tiles, which bounds peak memory and keeps every above-block_b request
    size numerically identical tile-for-tile.  What matters for the
    exactness contract is that dense and sparse share THIS function with
    the same block_b: whatever shape a request takes, both paths reduce
    over identical extents, so their bit-identity holds at every request
    size.  (A request with n ≤ block_b runs one (n, ·) kernel — its bits
    may differ from the same points inside a full tile, on both paths
    equally.)"""
    n = xs.shape[0]
    if n <= block_b:
        return block(xs)
    pad = (-n) % block_b
    xs_p = jnp.pad(xs, ((0, pad), (0, 0)))
    out = jax.lax.map(block, xs_p.reshape(-1, block_b, xs.shape[1]))
    return out.reshape((-1,) + out.shape[2:])[:n]


def _unstack_var(out: Array, return_var: bool):
    """Split the stacked [mean, var] kernel output into a (mean, var)
    pair; pass the plain mean through untouched."""
    if not return_var:
        return out
    return out[:, 0, :], out[:, 1, :]


@partial(jax.jit, static_argnames=("block_b", "return_var"))
def _predict_dense_jit(f: _CondFactors, sp: Array, active: Array,
                       xs_in: Array, block_b: int = 512,
                       return_var: bool = False) -> Array:
    """The dense batched eq. 27 kernel over a precomputed factor bundle:
    blocked (B, K) sweeps only — the factor stage already ran (fresh or
    from the FactorCache; same arrays either way)."""
    ni = f.mu_in.shape[1]

    def block(xb: Array) -> Array:
        return _dense_block(f, ni, sp, active, xb, return_var)

    return _map_blocks(block, xs_in, block_b)


def _empty_result(cfg: FIGMNConfig, o: int, return_var: bool):
    """The B = 0 contract: well-formed (0, o) outputs, no device dispatch
    (the blocked kernels would trace and launch for nothing — an empty
    request must cost nothing and crash nothing)."""
    z = jnp.zeros((0, o), cfg.dtype)
    return (z, z) if return_var else z


def predict(cfg: FIGMNConfig, state: FIGMNState, x_in: Array,
            idx_out) -> Array:
    """Reconstruct x[idx_out] from x_in (the remaining dims, in index order)."""
    require_nonempty(state)
    return predict_batch(cfg, state, jnp.asarray(x_in)[None, :],
                         idx_out)[0]


def predict_batch(cfg: FIGMNConfig, state: FIGMNState, xs_in: Array,
                  idx_out, return_var: bool = False,
                  factors: Optional[_CondFactors] = None,
                  block_b: int = 512):
    """(B, o) conditional means — factor stage + one blocked batched
    kernel (see module docstring), not a vmap of per-point calls.

    return_var=True additionally returns the (B, o) conditional variance
    as a (mean, var) pair.  ``factors`` injects a precomputed (typically
    cached) factor bundle; None computes it fresh through the same
    ``_factors_jit`` stage."""
    require_nonempty(state)
    xs_in = jnp.asarray(xs_in)
    targets = _as_targets(idx_out)
    if xs_in.shape[0] == 0:
        return _empty_result(cfg, len(targets), return_var)
    f = factors if factors is not None else _factors_jit(cfg, state,
                                                         targets)
    return _unstack_var(
        _predict_dense_jit(f, state.sp, state.active, xs_in,
                           block_b, return_var), return_var)


def predict_batch_routed(cfg: FIGMNConfig, state: FIGMNState, xs_in: Array,
                         idx_out, c: int = 0, cost_table=None,
                         device=None, return_var: bool = False,
                         factor_cache: Optional["FactorCache"] = None,
                         epoch: Optional[int] = None):
    """THE dense/sparse conditional dispatch every read front shares.

    c > 0 routes through the shortlisted kernel, c <= 0 through the dense
    one.  ``StreamRuntime.predict``, ``ScoringFrontend.predict`` and
    ``api.query.execute`` all call this one switch with their resolved
    width, so the tiers cannot drift apart in dispatch semantics — their
    equivalence is structural, not merely test-enforced.

    cost_table (a ``stream.costmodel.CostTable`` / path / None) makes the
    switch measured: when the table has dense AND sparse predict cells for
    this device key, the measured-faster path wins (at small K the bound
    pass + gather overhead can lose to the dense sweep).  With
    ``cost_table=None`` — the default every pre-existing caller hits —
    routing is byte-for-byte the historical ``c > 0`` rule.

    factor_cache + epoch amortise the factor stage: the bundle for
    (epoch, targets) is built once and reused for every request served
    against that epoch's state.  The caller owns the (state, epoch)
    pairing — it must capture both atomically (the serving frontend does,
    under its snapshot swap lock), because a cached bundle for epoch e
    answers ONLY against the state published as e."""
    require_nonempty(state)
    targets = _as_targets(idx_out)
    n = int(np.shape(xs_in)[0])
    if n == 0:
        return _empty_result(cfg, len(targets), return_var)
    if c > 0 and cost_table is not None:
        from repro.stream import costmodel   # lazy: stream imports core
        d = costmodel.resolve_predict(
            cfg, c=c, n=n, device=device, cost_table=cost_table)
        if d.path == "dense":
            c = 0
    factors = (factor_cache.get(cfg, state, targets, epoch)
               if factor_cache is not None and epoch is not None else None)
    if c > 0:
        return predict_batch_sparse(cfg, state, xs_in, targets, c=c,
                                    return_var=return_var, factors=factors)
    return predict_batch(cfg, state, xs_in, targets,
                         return_var=return_var, factors=factors)


class FactorCache:
    """Per-(epoch, targets-signature) LRU of eq. 27 factor bundles.

    The serving-cost amortisation of ROADMAP item 4: the factor stage
    (W⁻¹Z solve, Schur complement, marginal logdet, diag(W⁻¹)) depends
    only on (state, targets), and a served state only changes when a new
    snapshot epoch is published — so the bundle is built once per
    (epoch, targets) and every subsequent request pays the blocked batch
    kernel alone.  Invalidation rides the epoch key: a publish bumps the
    epoch, new requests miss onto fresh factors, and stale entries age
    out of the LRU — a cached bundle can never serve a newer epoch
    because the caller's (state, epoch) pair is captured atomically under
    the snapshot swap lock.

    Thread-safe: entries are immutable NamedTuples of jax arrays behind
    one mutex; a concurrent double-build on the same key is benign (both
    threads compute identical bits from the identical state and the last
    insert wins).  capacity <= 0 disables caching (every get computes
    fresh — still through the same two-stage kernels, so disabling the
    cache never changes results)."""

    def __init__(self, capacity: int = 16, registry=None):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Tuple[int, Tuple[int, ...]], _CondFactors]" \
            = OrderedDict()
        self.hits = 0
        self.misses = 0
        self._m_hits = self._m_misses = None
        if registry is not None:
            self._m_hits = registry.counter(
                "figmn_factor_cache_hits_total",
                "eq. 27 factor bundles served from cache")
            self._m_misses = registry.counter(
                "figmn_factor_cache_misses_total",
                "eq. 27 factor bundles built fresh")

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self):
        with self._lock:
            return list(self._entries)

    def get(self, cfg: FIGMNConfig, state: FIGMNState, idx_out_t,
            epoch: int) -> _CondFactors:
        """The factor bundle for (epoch, targets), building on miss."""
        targets = _as_targets(idx_out_t)
        if self.capacity <= 0:
            return _factors_jit(cfg, state, targets)
        key = (int(epoch), targets)
        with self._lock:
            f = self._entries.get(key)
            if f is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                if self._m_hits is not None:
                    self._m_hits.inc()
                return f
            self.misses += 1
            if self._m_misses is not None:
                self._m_misses.inc()
        f = _factors_jit(cfg, state, targets)   # build OUTSIDE the lock
        with self._lock:
            self._entries[key] = f
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
        return f

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


# ---------------------------------------------------------------------------
# Shortlisted conditional path — the PR-4 bound pass on the known-block
# marginal: O(K·D + C·D²·o) per point instead of O(K·D²·o).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("c", "block_b", "return_var"))
def _predict_sparse_jit(cfg: FIGMNConfig, f: _CondFactors, sp: Array,
                        active: Array, xs_in: Array, c: int,
                        block_b: int = 512,
                        return_var: bool = False) -> Array:
    ni = f.mu_in.shape[1]
    kpool = int(active.shape[0])
    # Bound pass on the KNOWN-BLOCK MARGINAL (same proxy family as
    # core.shortlist): diag of the Schur-complement precision stands in for
    # the full marginal Mahalanobis form, plus the marginal logdet +
    # log-prior bias the true posterior carries.  All O(K·i) per point,
    # matmul-spelled like shortlist._topc_exact_batch.
    diag_in = jnp.diagonal(f.prec_in, axis1=1, axis2=2)   # (K, i)
    bias = -0.5 * f.logdet_in + jnp.log(jnp.maximum(sp, 1e-30))
    dmu = diag_in * f.mu_in                               # (K, i)
    m2 = jnp.sum(dmu * f.mu_in, axis=1)                   # (K,)
    mu2 = jnp.sum(f.mu_in * f.mu_in, axis=1)              # (K,) (euclid)

    def block_sparse(xb: Array) -> Array:
        if cfg.shortlist_mode == "euclid":
            proxy = -0.5 * (jnp.sum(xb * xb, axis=1)[:, None]
                            - 2.0 * (xb @ f.mu_in.T) + mu2[None, :])
        else:
            d2_diag = (xb * xb) @ diag_in.T - 2.0 * (xb @ dmu.T) \
                + m2[None, :]
            proxy = bias[None, :] - 0.5 * d2_diag
        proxy = jnp.where(active[None, :], proxy, -jnp.inf)
        idx = jnp.sort(jax.lax.top_k(proxy, c)[1], axis=1)    # (B, C)
        diff = xb[:, None, :] - f.mu_in[idx]                  # (B, C, i)
        # same multiply+reduce spelling as the dense block (bit-identity)
        xhat = f.mu_out[idx] \
            - jnp.sum(f.winv_z[idx] * diff[:, :, None, :], axis=-1)
        t = jnp.einsum("bcij,bcj->bci", f.prec_in[idx], diff)
        d2 = jnp.einsum("bci,bci->bc", diff, t)
        logp = -0.5 * (ni * _LOG_2PI + f.logdet_in[idx] + d2)
        post = figmn.masked_posteriors(logp, sp[idx], active[idx])
        mean = jnp.einsum("bc,bco->bo", post, xhat)
        if not return_var:
            return mean
        ex2 = jnp.einsum("bc,bco->bo", post,
                         f.wdiag_inv[idx] + xhat * xhat)
        return jnp.stack([mean, jnp.maximum(ex2 - mean * mean, 0.0)],
                         axis=1)

    def block_dense(xb: Array) -> Array:
        return _dense_block(f, ni, sp, active, xb, return_var)

    # C covering the pool ⇒ the sorted shortlist IS the identity
    # permutation: skip the bound pass + gather and run the shared dense
    # block body — bit-identity with predict_batch by construction (and
    # strictly faster than gathering every row).  C covering only the
    # ACTIVE slots drops no mass either, but the gathered (B, C) einsums
    # may reduce in another order than the dense (K,) ones: run the dense
    # body there too, so C ≥ active K is bit-identical at any K.
    if c >= kpool:
        block = block_dense
    else:
        covered = jnp.sum(active) <= c

        def block(xb: Array) -> Array:
            return jax.lax.cond(covered, block_dense, block_sparse, xb)
    return _map_blocks(block, xs_in, block_b)


def predict_batch_sparse(cfg: FIGMNConfig, state: FIGMNState, xs_in: Array,
                         idx_out, c: int | None = None,
                         block_b: int = 512, return_var: bool = False,
                         factors: Optional[_CondFactors] = None):
    """(B, o) conditional means with a top-C component shortlist.

    An O(K·i) bound pass on the known-block marginal ranks the slots per
    point; the exact eq. 27 work (conditional mean, Schur-complement
    Mahalanobis, masked posterior) runs on the C gathered rows only.

    Exactness contract (tests/test_api.py, same pattern as the shortlisted
    score/fit paths): whenever C covers the active slots (C ≥ active K)
    the SAME dense block body runs, so the result is BIT-IDENTICAL to
    ``predict_batch`` at any batch size; with C covering the whole pool
    that is decided when tracing, below it by a ``lax.cond`` on the
    active count (pinned on the golden streams).  Below active K the
    truncation drops only numerically-zero posterior tail mass.
    """
    require_nonempty(state)
    kpool = int(state.active.shape[0])
    c = min(int(cfg.shortlist_c if c is None else c), kpool)
    if c <= 0:
        raise ValueError("predict_batch_sparse needs a positive shortlist "
                         "width (cfg.shortlist_c or the c argument)")
    xs_in = jnp.asarray(xs_in)
    targets = _as_targets(idx_out)
    if xs_in.shape[0] == 0:
        return _empty_result(cfg, len(targets), return_var)
    f = factors if factors is not None else _factors_jit(cfg, state,
                                                         targets)
    return _unstack_var(
        _predict_sparse_jit(cfg, f, state.sp, state.active, xs_in, c,
                            block_b, return_var), return_var)


# ---------------------------------------------------------------------------
# Covariance-form baseline (eq. 15) — O(KD³) per query.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("idx_out_t",))
def _predict_ref_batch_jit(cfg: FIGMNConfig, state: IGMNState, xs_in: Array,
                           idx_out_t: Tuple[int, ...]) -> Array:
    idx_in, idx_out = _split_indices(cfg.dim, np.asarray(idx_out_t))
    cov = state.cov
    C_i = cov[:, idx_in[:, None], idx_in[None, :]]      # (K, i, i)
    C_ti = cov[:, idx_out[:, None], idx_in[None, :]]    # (K, o, i)
    diff = xs_in[:, None, :] - state.mu[None, :, idx_in]

    sol = jnp.linalg.solve(C_i[None], diff[..., None])[..., 0]   # O(D³)
    xhat = state.mu[None, :, idx_out] \
        + jnp.einsum("koi,bki->bko", C_ti, sol)

    d2 = jnp.einsum("bki,bki->bk", diff, sol)
    _, logdet_ci = jnp.linalg.slogdet(C_i)                       # O(D³)
    ni = idx_in.shape[0]
    logp = -0.5 * (ni * _LOG_2PI + logdet_ci[None, :] + d2)
    post = figmn.masked_posteriors(logp, state.sp, state.active)
    return jnp.einsum("bk,bko->bo", post, xhat)


def predict_ref(cfg: FIGMNConfig, state: IGMNState, x_in: Array,
                idx_out) -> Array:
    require_nonempty(state)
    return _predict_ref_batch_jit(cfg, state, jnp.asarray(x_in)[None, :],
                                  _as_targets(idx_out))[0]


def predict_ref_batch(cfg: FIGMNConfig, state: IGMNState, xs_in: Array,
                      idx_out) -> Array:
    require_nonempty(state)
    return _predict_ref_batch_jit(cfg, state, jnp.asarray(xs_in),
                                  _as_targets(idx_out))
