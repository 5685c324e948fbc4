"""Shared neural-net layers: norms, RoPE/M-RoPE, GQA attention (full / SWA /
cross), gated MLPs, embeddings.

Everything is a pure function over explicit parameter dicts (no module
framework): params are pytrees built by ``transformer.param_defs`` and
layer weights arrive stacked over the layer axis for ``lax.scan``.

Numerics: activations/params in cfg.dtype (bf16 by default), attention
logits+softmax and final logits in f32 — standard TPU recipe.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.distributed.sharding import constrain

Array = jax.Array


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    """(head_dim/2,) inverse frequencies, f32."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, T, H, hd); positions: (B, T) int32 → same shape, rotated."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                      # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B,T,half)
    cos = jnp.cos(angles)[:, :, None, :]                        # (B,T,1,half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: Array, positions3: Array, theta: float,
                sections: Tuple[int, ...]) -> Array:
    """Qwen2-VL multimodal RoPE.

    positions3: (3, B, T) — temporal / height / width position ids.  The
    head_dim/2 frequency slots are split into ``sections`` (t, h, w); each
    section rotates by its own position stream.  Text tokens carry identical
    t/h/w ids, reducing to vanilla RoPE.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)                      # (half,)
    angle_streams = positions3[..., None].astype(jnp.float32) * freqs
    # (3, B, T, half) → pick stream per frequency slot
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=half)
    pick = jax.nn.one_hot(sec_id, len(sections), dtype=jnp.float32)  # (half,3)
    angles = jnp.einsum("sbth,hs->bth", angle_streams, pick)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA; full / sliding-window / cross)
# ---------------------------------------------------------------------------

ATTN_KV_CHUNK = 512


def _attn_one_chunk(q, k, v, q_pos, k_pos, causal, window, k_valid, scale):
    """Un-chunked core: returns (unnormalised ctx, row max m, row sum l)."""
    b, t, kvh, g, hd = q.shape
    logits = jnp.einsum("btkgh,bskh->bkgts", q, k,
                        preferred_element_type=jnp.float32) * scale
    dpos = q_pos[:, :, None] - k_pos[:, None, :]                 # (B, T, Sc)
    mask = jnp.ones(dpos.shape, bool)
    if causal:
        mask &= dpos >= 0
    window = jnp.asarray(window)
    mask &= (window <= 0) | (dpos < window)
    if k_valid is not None:
        mask &= k_valid[:, None, :]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    m = jnp.max(logits, axis=-1)                                 # (B,KV,g,T)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    ctx = jnp.einsum("bkgts,bskh->btkgh", p.astype(v.dtype), v)
    return ctx, m, l


def attention(q: Array, k: Array, v: Array,
              q_pos: Array, k_pos: Array,
              causal: bool = True,
              window: Array | int = 0,
              k_valid: Optional[Array] = None,
              kv_chunk: int = ATTN_KV_CHUNK) -> Array:
    """Grouped-query attention with online-softmax chunking over keys.

    q: (B, T, H, hd);  k, v: (B, S, KV, hd);  q_pos: (B, T);  k_pos: (B, S).
    window: 0 → full; w > 0 → sliding window of width w.  May be a traced
    scalar (per-layer window pattern inside lax.scan).
    k_valid: (B, S) bool — mask for ring-buffer/padded cache slots.

    The key axis is processed in chunks with the running (max, sum, ctx)
    rescaling of flash attention, so the (T × S) logit matrix is never
    materialised — at 32k context the full matrix would be ~17 GB/device,
    far beyond HBM; chunking keeps the transient at T × kv_chunk.
    Returns (B, T, H, hd).
    """
    b, t, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, t, kvh, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    hd_v = v.shape[-1]
    # Decode (t == 1): never chunk.  The (B, H, 1, S) logits are tiny, and
    # chunking's (n_chunks, chunk, ...) reshape of an S-sharded cache forces
    # GSPMD into a full cache all-gather (§Perf iteration 2: this single
    # change removed ~95% of decode collective bytes).
    if s <= kv_chunk or t == 1:
        ctx, m, l = _attn_one_chunk(qg, k, v, q_pos, k_pos, causal, window,
                                    k_valid, scale)
        out = ctx.astype(jnp.float32) \
            / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return out.astype(q.dtype).reshape(b, t, h, hd_v)

    assert s % kv_chunk == 0, (s, kv_chunk)
    n_chunks = s // kv_chunk
    rs = lambda a: a.reshape(a.shape[0], n_chunks, kv_chunk,
                             *a.shape[2:]).swapaxes(0, 1)
    k_c, v_c = rs(k), rs(v)
    kp_c = k_pos.reshape(b, n_chunks, kv_chunk).swapaxes(0, 1)
    kv_valid_c = None if k_valid is None else \
        k_valid.reshape(b, n_chunks, kv_chunk).swapaxes(0, 1)

    m0 = jnp.full((b, kvh, g, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, t), jnp.float32)
    acc0 = jnp.zeros((b, t, kvh, g, hd_v), jnp.float32)

    def body(carry, chunk):
        m_run, l_run, acc = carry
        if k_valid is None:
            kc, vc, kpc = chunk
            kvc = None
        else:
            kc, vc, kpc, kvc = chunk
        ctx, m_c, l_c = _attn_one_chunk(qg, kc, vc, q_pos, kpc, causal,
                                        window, kvc, scale)
        m_new = jnp.maximum(m_run, m_c)
        a_old = jnp.exp(m_run - m_new)
        a_new = jnp.exp(m_c - m_new)
        l_new = l_run * a_old + l_c * a_new
        acc = acc * a_old.transpose(0, 3, 1, 2)[..., None] \
            + ctx.astype(jnp.float32) * a_new.transpose(0, 3, 1, 2)[..., None]
        return (m_new, l_new, acc), None

    chunks = (k_c, v_c, kp_c) if k_valid is None \
        else (k_c, v_c, kp_c, kv_valid_c)
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, acc0), chunks)
    out = acc / jnp.maximum(l_f, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype).reshape(b, t, h, hd_v)


def gqa_project(x: Array, wq: Array, wk: Array, wv: Array,
                qk_norm_scales: Optional[Tuple[Array, Array]] = None
                ) -> Tuple[Array, Array, Array]:
    """x: (B,T,D) → q (B,T,H,hd), k/v (B,T,KV,hd)."""
    q = jnp.einsum("btd,dhk->bthk", x, wq)
    k = jnp.einsum("btd,dhk->bthk", x, wk)
    v = jnp.einsum("btd,dhk->bthk", x, wv)
    if qk_norm_scales is not None:
        q = rms_norm(q, qk_norm_scales[0])
        k = rms_norm(k, qk_norm_scales[1])
    return q, k, v


def attn_out(attn: Array, wo: Array) -> Array:
    return jnp.einsum("bthk,hkd->btd", attn, wo)


# Global attention implementation switch for the TRAIN/PREFILL-no-cache
# path: "xla" (chunked online-softmax above) or "flash" (Pallas kernel,
# kernels/flash_attention.py — §Perf iteration on the train cells).
ATTN_IMPL = "xla"


def attention_trainpath(q: Array, k: Array, v: Array, q_pos: Array,
                        k_pos: Array, window: Array | int = 0) -> Array:
    """Causal self-attention for the no-cache path, honouring ATTN_IMPL.

    Flash path: GQA kv heads are expanded to the q heads (a cheap gather —
    after tensor-parallel sharding the per-device q-head count is small),
    then the Pallas kernel runs per device inside shard_map.
    """
    if ATTN_IMPL != "flash":
        return attention(q, k, v, q_pos, k_pos, causal=True, window=window)
    from repro.distributed.sharding import active_mesh, resolve_spec
    from repro.kernels.flash_attention import flash_attention
    import functools
    from jax.sharding import PartitionSpec as P

    b, t, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    idx = jnp.arange(h) // g
    k = jnp.take(k, idx, axis=2)                    # (B, S, H, hd)
    v = jnp.take(v, idx, axis=2)
    interp = jax.default_backend() == "cpu"
    win = jnp.asarray(window, jnp.int32)

    mesh = active_mesh()
    if mesh is None:
        return flash_attention(q, k, v, q_pos, k_pos, win,
                               causal=True, interpret=interp)
    qs = resolve_spec(("batch", None, "heads", None), q.shape)
    ps = resolve_spec(("batch", None), q_pos.shape)
    fn = compat.shard_map(
        functools.partial(flash_attention, causal=True, interpret=interp),
        mesh=mesh,
        in_specs=(qs, qs, qs, ps, ps, P()),
        out_specs=qs)
    return fn(q, k, v, q_pos, k_pos, win)


# ---------------------------------------------------------------------------
# Gated MLPs
# ---------------------------------------------------------------------------

def gated_mlp(x: Array, w_gate: Array, w_up: Array, w_down: Array,
              act: str = "silu") -> Array:
    """SwiGLU (act=silu) / GeGLU (act=gelu): down(act(gate(x)) * up(x))."""
    g = jnp.einsum("btd,df->btf", x, w_gate)
    u = jnp.einsum("btd,df->btf", x, w_up)
    g = constrain(g, ("batch", None, "mlp"))
    u = constrain(u, ("batch", None, "mlp"))
    if act == "gelu":
        h = jax.nn.gelu(g.astype(jnp.float32), approximate=True).astype(x.dtype) * u
    else:
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("btf,fd->btd", h, w_down)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed(tokens: Array, table: Array, scale: bool = False) -> Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.sqrt(jnp.asarray(table.shape[1], jnp.float32)).astype(x.dtype)
    return x


def unembed(x: Array, table_or_head: Array, tied: bool) -> Array:
    """→ f32 logits.  tied: table is (V, D); untied: head is (D, V)."""
    if tied:
        return jnp.einsum("btd,vd->btv", x, table_or_head,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("btd,dv->btv", x, table_or_head,
                      preferred_element_type=jnp.float32)
