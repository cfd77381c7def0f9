"""Grouped-query attention with RoPE, sliding windows, KV caches, and
cross-attention — shared by every attention-bearing architecture."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, dense_init

Array = jax.Array

NEG_INF = -2.0e38


def attn_init(key, cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, nq)),
        "wk": dense_init(k2, (d, nkv)),
        "wv": dense_init(k3, (d, nkv)),
        "wo": dense_init(k4, (nq, d)),
    }


def _split_heads(x: Array, n: int, hd: int) -> Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def _gqa_scores(q: Array, k: Array) -> Array:
    """q: (B,S,KV,G,D), k: (B,T,KV,D) -> (B,KV,G,S,T)."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k)


def _gqa_out(w: Array, v: Array) -> Array:
    """w: (B,KV,G,S,T), v: (B,T,KV,D) -> (B,S,KV,G,D)."""
    return jnp.einsum("bkgst,btkd->bskgd", w, v)


def _softmax(scores: Array) -> Array:
    s = scores.astype(jnp.float32)
    s = s - jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    w = jnp.exp(s)
    return (w / jnp.sum(w, axis=-1, keepdims=True))


# ------------------------------------------------------- blockwise attention

BLOCK_T = 512
# use the blockwise path once the (S, T) score matrix would exceed this
FLASH_THRESHOLD = 4096 * 4096


def _blockwise_attention(
    q: Array,            # (B,S,KV,G,D), already scaled
    k: Array,            # (B,T,KV,D)
    v: Array,            # (B,T,KV,D)
    qpos: Array,         # (B,S) absolute query positions
    kpos: Array,         # (B,T) absolute key positions
    window: int,
) -> Array:
    """Flash-semantics attention: lax.scan over KV blocks with running
    (max, denom, acc) — the S×T score matrix is never materialised, only a
    (B,KV,G,S,BLOCK_T) transient per step. The KV-block body is rematted so
    the backward pass recomputes block scores instead of storing them."""
    B, S, KV, G, D = q.shape
    T = k.shape[1]
    pad = (-T) % BLOCK_T
    nblk = (T + pad) // BLOCK_T
    SENTINEL = jnp.iinfo(jnp.int32).max

    def blocked(x, fill=0.0):
        cfg = [(0, 0)] * x.ndim
        cfg[1] = (0, pad)
        x = jnp.pad(x, cfg, constant_values=fill)
        return jnp.moveaxis(
            x.reshape(x.shape[0], nblk, BLOCK_T, *x.shape[2:]), 1, 0)

    kb, vb = blocked(k), blocked(v)                      # (nblk,B,BT,KV,D)
    kpb = blocked(kpos.astype(jnp.int32), fill=SENTINEL)  # (nblk,B,BT)
    qp = qpos[:, None, None, :, None]                    # (B,1,1,S,1)

    def body(carry, inp):
        m, l, acc = carry
        k_i, v_i, kp_i = inp
        s = jnp.einsum("bskgd,btkd->bkgst", q, k_i).astype(jnp.float32)
        tp = kp_i[:, None, None, None, :]                # (B,1,1,1,BT)
        mask = (tp <= qp) & (tp != SENTINEL)
        if window:
            mask &= (qp - tp) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(v_i.dtype), v_i
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    a0 = jnp.zeros((B, KV, G, S, D), jnp.float32)
    body = jax.checkpoint(body, prevent_cse=False)
    (_, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, kpb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,KV,G,S,D)
    out = jnp.moveaxis(out, 3, 1)                        # (B,S,KV,G,D)
    return out.astype(v.dtype).reshape(B, S, KV * G * D)


def _attend(q, k, v, qpos, kpos, window) -> Array:
    """Dispatch between direct and blockwise attention.
    q: (B,S,KV,G,D) scaled; k/v: (B,T,KV,D); qpos/kpos None => non-causal.
    Returns (B,S,H*D)."""
    B, S, KV, G, D = q.shape
    T = k.shape[1]
    causal = qpos is not None
    if S * T > FLASH_THRESHOLD:
        if not causal:  # non-causal: all positions visible, pad masked out
            qpos = jnp.full((1, S), T, jnp.int32)
            kpos = jnp.arange(T, dtype=jnp.int32)[None, :]
        return _blockwise_attention(q, k, v, qpos, kpos, window)
    scores = _gqa_scores(q, k)
    if causal:
        tp = kpos[:, None, None, None, :]
        qp = qpos[:, None, None, :, None]
        mask = tp <= qp
        if window:
            mask &= (qp - tp) < window
        scores = jnp.where(mask, scores, NEG_INF)
    w = _softmax(scores).astype(v.dtype)
    o = _gqa_out(w, v)
    return o.reshape(B, S, KV * G * D)


def self_attention(
    p: dict,
    cfg: ModelConfig,
    x: Array,
    *,
    positions: Array,             # (B, S) absolute positions of queries
    window: int = 0,              # 0 => global causal
    theta: float | None = None,
    cache: Optional[dict] = None,  # decode: {"k","v","pos"} rolling buffers
    layer: tuple = (),            # decode: this layer's index into `cache`
) -> tuple[Array, Optional[dict]]:
    """Causal (optionally sliding-window) GQA self-attention.

    Train/prefill: cache is None -> attends within the sequence, returns the
    (rope-applied) K/V so the caller can build a cache.
    Decode: cache given, S == 1 -> writes the new token's K, V and position
    into the rolling buffer and attends over it. ``k`` and ``v`` are
    (..., B, W, KV*hd), one row per slot, and ``pos`` is (..., B, W);
    ``layer`` indexes their leading axes (``(i,)`` for layer i of a stacked
    cache, ``()`` for a buffer of its own). The whole buffers come back with
    one row per batch element written in place.
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    th = cfg.rope_theta if theta is None else theta

    q = _split_heads(x @ p["wq"], H, hd)
    k = _split_heads(x @ p["wk"], KV, hd)
    v = _split_heads(x @ p["wv"], KV, hd)
    q = apply_rope(q, positions, th, cfg.partial_rotary)
    k = apply_rope(k, positions, th, cfg.partial_rotary)
    q = q.reshape(B, S, KV, G, hd) * (hd ** -0.5)

    if cache is None:
        kv_pos = positions                                     # (B, S)
        o = _attend(q, k, v, positions, kv_pos, window)        # (B,S,H*hd)
        new_cache = {"k": k, "v": v, "pos": kv_pos.astype(jnp.int32)}
        return o @ p["wo"], new_cache

    # ---------------- decode: S == 1, rolling buffer of width Wbuf
    Wbuf, C = cache["k"].shape[-2:]                            # C = KV*hd
    qpos = positions[:, 0]                                     # (B,)
    row = layer + (jnp.arange(B), (qpos % Wbuf).astype(jnp.int32))
    with jax.named_scope("kv_cache"):
        ck = cache["k"].at[row].set(k.reshape(B, C))
        cv = cache["v"].at[row].set(v.reshape(B, C))
        cpos = cache["pos"].at[row].set(qpos.astype(jnp.int32))
    # The layer's rows are read as stored, (B, W, KV*hd). Each query head
    # is laid over the whole row, zero outside its own KV group, so its
    # score sums its own group's products; of the weighted sum over the
    # rows each head keeps its own group's part. The 0/1 products are
    # exact, and both contractions over the cache run along the row's
    # minor axis, so the cache needs no relayout.
    own = (jnp.arange(C) // hd == jnp.arange(KV)[:, None, None]
           ).astype(q.dtype)                                   # (KV,1,C)
    spread = (jnp.arange(C)[:, None] % hd == jnp.arange(hd)
              ).astype(q.dtype)                                # (C,hd)
    qr = jnp.einsum("bkgd,cd->bkgc", q[:, 0], spread) * own    # (B,KV,G,C)
    scores = jnp.einsum("bkgc,bwc->bkgw", qr, ck[layer])       # (B,KV,G,W)
    tp = cpos[layer][:, None, None, :]
    qp = qpos[:, None, None, None]
    mask = (tp >= 0) & (tp <= qp)
    if window:
        mask &= (qp - tp) < window
    scores = jnp.where(mask, scores, NEG_INF)
    w = _softmax(scores).astype(v.dtype)
    o = jnp.einsum("bkgw,bwc->bkgc", w, cv[layer]) * own       # (B,KV,G,C)
    o = jnp.einsum("bkgc,cd->bkgd", o, spread).reshape(B, 1, H * hd)
    return o @ p["wo"], {"k": ck, "v": cv, "pos": cpos}


def cross_attention(p: dict, cfg: ModelConfig, x: Array, ctx: Array) -> Array:
    """Cross-attention onto a static context (image patches / encoder out).
    No positional rotation (context is an unordered/pre-encoded set)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    q = _split_heads(x @ p["wq"], H, hd).reshape(B, S, KV, G, hd) * (hd ** -0.5)
    k = _split_heads(ctx @ p["wk"], KV, hd)
    v = _split_heads(ctx @ p["wv"], KV, hd)
    o = _attend(q, k, v, None, None, 0)
    return o @ p["wo"]


def encoder_self_attention(p: dict, cfg: ModelConfig, x: Array) -> Array:
    """Bidirectional (non-causal) self-attention for encoder stacks."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    G = H // KV
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = _split_heads(x @ p["wq"], H, hd)
    k = _split_heads(x @ p["wk"], KV, hd)
    v = _split_heads(x @ p["wv"], KV, hd)
    q = apply_rope(q, pos, cfg.rope_theta, cfg.partial_rotary)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.partial_rotary)
    q = q.reshape(B, S, KV, G, hd) * (hd ** -0.5)
    o = _attend(q, k, v, None, None, 0)
    return o @ p["wo"]
