"""Plain reference for a dense decoder-only transformer with grouped-query
attention, (partial) rotary positions and a gated SiLU MLP: granite-3.0
and chatglm3 as their configuration files state them.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no batching tricks. It is teacher-forced over whole
sequences and returns the logits at the positions asked for. It imports
nothing of the program under test. It reads the weights by name from the
parameter tree the benchmark made (`weights.py`), whose layout is:

- ``embed`` (V_padded, d); ``lm_head`` (d, V_padded) unless tied;
  ``final_norm`` (d,);
- ``periods/l0/{norm1,norm2}`` (L, d); ``periods/l0/mixer/{wq,wk,wv}``
  (L, d, heads·hd); ``.../mixer/wo`` (L, heads·hd, d);
  ``periods/l0/ffn/{wi_gate,wi_up}`` (L, d, d_ff); ``.../ffn/wo``
  (L, d_ff, d).

A norm's stored scale is an offset from one: RMSNorm multiplies by
``1 + scale``. Rows of the embedding past the vocabulary are padding and
never score. Query head h reads KV head h // (heads / kv_heads). Rotary
rotates the leading ``rotary_dim`` of each head, as two halves.

``quantize`` gives the control: every matrix rounded to float8 (e4m3)
with one scale per output column, then computed as above."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(conf: dict) -> dict:
    """The sizes and constants of a configuration file, under one set of
    names, whatever the source's own key names are."""
    kind = conf["model_type"]
    if kind == "granite":
        d, heads = conf["hidden_size"], conf["num_attention_heads"]
        hd = d // heads
        return {
            "d_model": d, "n_layers": conf["num_hidden_layers"],
            "n_heads": heads, "n_kv_heads": conf["num_key_value_heads"],
            "head_dim": hd, "d_ff": conf["intermediate_size"],
            "vocab": conf["vocab_size"],
            "tie_embeddings": conf["tie_word_embeddings"],
            "rotary_dim": hd, "rope_theta": conf["rope_theta"],
            "norm_eps": conf["rms_norm_eps"],
            "attn_scale": conf["attention_multiplier"],
            "embed_scale": conf["embedding_multiplier"],
            "residual_scale": conf["residual_multiplier"],
            "logit_divisor": conf["logits_scaling"],
            "bytes_per_param": 2,
        }
    if kind == "chatglm":
        d, heads = conf["hidden_size"], conf["num_attention_heads"]
        hd = conf["kv_channels"]
        return {
            "d_model": d, "n_layers": conf["num_layers"],
            "n_heads": heads,
            "n_kv_heads": (conf["multi_query_group_num"]
                           if conf["multi_query_attention"] else heads),
            "head_dim": hd, "d_ff": conf["ffn_hidden_size"],
            "vocab": conf["padded_vocab_size"],
            "tie_embeddings": conf["tie_word_embeddings"],
            # chatglm rotates half of each head ("2D" rotary)
            "rotary_dim": hd // 2, "rope_theta": conf["rope_theta"],
            "norm_eps": conf["layernorm_epsilon"],
            "attn_scale": hd ** -0.5, "embed_scale": 1.0,
            "residual_scale": 1.0, "logit_divisor": 1.0,
            "bytes_per_param": 2,
        }
    raise ValueError(f"dense_decoder has no reading for {kind!r}")


def _f32(w, quantize: bool):
    w = w.astype(jnp.float32)
    if not quantize:
        return w
    # one scale per output column (the last axis); e4m3 tops out at 448
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale


def _norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, positions, rot: int, theta: float):
    """x: (n, S, heads, hd); rotate the leading ``rot`` dims as halves."""
    if rot == 0:
        return x
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def logits(params: dict, dm: dict, tokens, first: int,
           quantize: bool = False):
    """Float32 logits (n, S - first, vocab) at positions first .. S-1 of
    ``tokens`` (n, S)."""
    n, S = tokens.shape
    H, KV, hd = dm["n_heads"], dm["n_kv_heads"], dm["head_dim"]
    G = H // KV
    eps = dm["norm_eps"]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                       # (S, T)

    emb = params["embed"][: dm["vocab"]]
    x = emb[tokens].astype(jnp.float32) * dm["embed_scale"]

    def layer(x, lp):
        m, f = lp["mixer"], lp["ffn"]
        h = _norm(x, lp["norm1"], eps)
        q = _mm(h, _f32(m["wq"], quantize)).reshape(n, S, H, hd)
        k = _mm(h, _f32(m["wk"], quantize)).reshape(n, S, KV, hd)
        v = _mm(h, _f32(m["wv"], quantize)).reshape(n, S, KV, hd)
        q = _rope(q, pos, dm["rotary_dim"], dm["rope_theta"])
        k = _rope(k, pos, dm["rotary_dim"], dm["rope_theta"])
        k = jnp.repeat(k, G, axis=2)                            # head h -> h//G
        v = jnp.repeat(v, G, axis=2)
        s = jnp.einsum("nshd,nthd->nhst", q, k,
                       precision=HIGHEST) * dm["attn_scale"]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("nhst,nthd->nshd", w, v,
                       precision=HIGHEST).reshape(n, S, H * hd)
        x = x + dm["residual_scale"] * _mm(o, _f32(m["wo"], quantize))
        h2 = _norm(x, lp["norm2"], eps)
        g = jax.nn.silu(_mm(h2, _f32(f["wi_gate"], quantize)))
        u = _mm(h2, _f32(f["wi_up"], quantize))
        x = x + dm["residual_scale"] * _mm(g * u, _f32(f["wo"], quantize))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["periods"]["l0"])
    x = _norm(x[:, first:], params["final_norm"], eps)
    head = (emb.T if dm["tie_embeddings"]
            else params["lm_head"][:, : dm["vocab"]])
    return _mm(x, _f32(head, quantize)) / dm["logit_divisor"]
