"""Plain reference for a Mamba-2/attention hybrid decoder: granite-4.0-h
as its configuration file states it.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no cache, no chunking. It is teacher-forced over whole sequences
and returns the logits at the positions asked for. It imports nothing of
the program under test. The Mamba-2 layers run the token-by-token
recurrence (a ``lax.scan`` over time); the program's prefill runs the
chunked SSD form, so the two share no algorithm.

Layers follow ``layer_types``. Every layer is ``x + mixer(norm1(x))`` and
then ``x + mlp(norm2(x))``, with a gated SiLU MLP. Attention is GQA (query
head h reads KV head h // (heads / kv_heads)) with no positions
(``position_embedding_type`` ``nope``). A Mamba-2 mixer, per token t and
head h reading group g = h // (heads / groups) of B and C, with
``in_proj`` split as [z (di), xBC (di + 2·G·N), dt (H)]:

    xBC  = silu(causal_conv(xBC) + conv_b) = [x (H×P), B (G×N), C (G×N)]
    dt_h = softplus(dt_h + dt_bias_h),    A_h = -exp(A_log_h)
    S_h <- exp(dt_h·A_h)·S_h + dt_h·x_h ⊗ B_g
    y_h  = S_h·C_g + D_h·x_h
    out  = out_proj(RMSNorm(y ⊙ silu(z)))    (over all di channels)

It reads the weights by name from the parameter tree the benchmark made
(`weights.py`), whose layout is:

- ``embed`` (V_padded, d), the head as well (tied); ``final_norm`` (d,);
- ``periods/l<j>`` for j in one period of ``layer_types``, every leaf
  stacked over the periods: layer k is ``periods/l<k mod period>`` at
  index k // period;
- an attention layer: ``mixer/{wq,wk,wv}`` (d, heads·hd), ``mixer/wo``
  (heads·hd, d);
- a Mamba-2 layer: ``mixer/in_proj`` (d, 2·di + 2·G·N + H),
  ``mixer/conv_w`` (K, di + 2·G·N), ``mixer/conv_b``,
  ``mixer/{dt_bias,A_log,D}`` (H,), ``mixer/norm`` (di,),
  ``mixer/out_proj`` (di, d);
- every layer: ``norm1``, ``norm2`` (d,), ``ffn/{wi_gate,wi_up}`` (d, d_ff),
  ``ffn/wo`` (d_ff, d).

A norm's stored scale is an offset from one. Rows of the embedding past the
vocabulary are padding and never score.

``quantize`` gives the control: every matrix (the conv taps too) rounded
to float8 (e4m3) with one scale per output column, then computed as
above."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
STATE_BYTES = {"float32": 4, "bfloat16": 2}


def dims(conf: dict) -> dict:
    """The sizes and constants of a configuration file, under the names the
    benchmark's dense counts read (`work.py`), with the Mamba-2 sizes and
    the layer pattern beside them."""
    if conf["model_type"] != "granitemoehybrid":
        raise ValueError(f"granite_hybrid has no reading for "
                         f"{conf['model_type']!r}")
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    hd = d // heads
    H, P = conf["mamba_n_heads"], conf["mamba_d_head"]
    if H * P != conf["mamba_expand"] * d:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * d")
    if conf["num_local_experts"]:
        raise ValueError("granite_hybrid reads no experts")
    nope = conf["position_embedding_type"] == "nope"
    return {
        "d_model": d, "n_layers": conf["num_hidden_layers"],
        "n_heads": heads, "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": hd, "d_ff": conf["shared_intermediate_size"],
        "vocab": conf["vocab_size"],
        "tie_embeddings": conf["tie_word_embeddings"],
        "rotary_dim": 0 if nope else hd, "rope_theta": conf["rope_theta"],
        "norm_eps": conf["rms_norm_eps"],
        "attn_scale": conf["attention_multiplier"],
        "embed_scale": conf["embedding_multiplier"],
        "residual_scale": conf["residual_multiplier"],
        "logit_divisor": conf["logits_scaling"],
        "bytes_per_param": 2,
        "layer_types": tuple(conf["layer_types"]),
        "ssm_heads": H, "ssm_head_dim": P,
        "ssm_groups": conf["mamba_n_groups"],
        "ssm_state": conf["mamba_d_state"], "ssm_conv": conf["mamba_d_conv"],
        "ssm_chunk": conf["mamba_chunk_size"],
        "ssm_state_bytes": STATE_BYTES[conf["ssm_state_dtype"]],
        "conv_state_bytes": STATE_BYTES[conf["conv_state_dtype"]],
    }


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


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _attention(m, dm, h, quantize):
    n, S, _ = h.shape
    H, KV, hd = dm["n_heads"], dm["n_kv_heads"], dm["head_dim"]
    if dm["rotary_dim"]:
        raise ValueError("granite_hybrid's attention has no positions")
    q = _mm(h, _f32(m["wq"], quantize)).reshape(n, S, H, hd)
    k = _mm(h, _f32(m["wk"], quantize)).reshape(n, S, KV, hd)
    v = _mm(h, _f32(m["wv"], quantize)).reshape(n, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)                      # head h -> h//G
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("nshd,nthd->nhst", q, k,
                   precision=HIGHEST) * dm["attn_scale"]
    pos = jnp.arange(S)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("nhst,nthd->nshd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(n, S, H * hd)
    return _mm(o, _f32(m["wo"], quantize))


def _mamba2(m, dm, h, quantize):
    n, S, _ = h.shape
    H, P, G, N = (dm["ssm_heads"], dm["ssm_head_dim"], dm["ssm_groups"],
                  dm["ssm_state"])
    K, di = dm["ssm_conv"], H * P
    C_dim = di + 2 * G * N
    if m["in_proj"].shape[-1] != di + C_dim + H \
            or m["conv_w"].shape != (K, C_dim) or m["A_log"].shape != (H,):
        raise ValueError("Mamba-2 weights do not have the file's sizes")
    zxbcdt = _mm(h, _f32(m["in_proj"], quantize))
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + C_dim],
                  zxbcdt[..., di + C_dim:])
    # causal depthwise conv: out_t = sum_k w[k] · xBC_{t-(K-1)+k} + b
    w = _f32(m["conv_w"], quantize)
    xpad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xpad[:, k:k + S] * w[k] for k in range(K))
    xbc = jax.nn.silu(conv + m["conv_b"].astype(jnp.float32))
    x = xbc[..., :di].reshape(n, S, H, P)
    group = jnp.arange(H) // (H // G)                       # head -> group
    Bh = xbc[..., di:di + G * N].reshape(n, S, G, N)[:, :, group]
    Ch = xbc[..., di + G * N:].reshape(n, S, G, N)[:, :, group]
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(jnp.float32))  # (n,S,H)
    A = -jnp.exp(m["A_log"].astype(jnp.float32))
    Dh = m["D"].astype(jnp.float32)

    def step(state, inp):                                   # (n, H, P, N)
        x_t, B_t, C_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, :, None, :])
        y = jnp.einsum("nhpk,nhk->nhp", state, C_t, precision=HIGHEST)
        return state, y + Dh[:, None] * x_t

    state0 = jnp.zeros((n, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, state0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, Bh, Ch, dt)))
    y = jnp.moveaxis(ys, 0, 1).reshape(n, S, di)
    y = _norm(y * jax.nn.silu(z), m["norm"], dm["norm_eps"])
    return _mm(y, _f32(m["out_proj"], quantize))


def logits(params: dict, dm: dict, tokens, first: int,
           quantize: bool = False):
    """Float32 logits (n, S - first, vocab) at positions first .. S-1 of
    ``tokens`` (n, S)."""
    types = dm["layer_types"]
    period = len(params["periods"])
    if len(types) != dm["n_layers"] or len(types) % period:
        raise ValueError("layer_types does not fill whole periods")
    kinds = types[:period]
    if types != kinds * (len(types) // period):
        raise ValueError("layer_types does not repeat the stored period")
    for j, kind in enumerate(kinds):
        stored = "in_proj" in params["periods"][f"l{j}"]["mixer"]
        if stored != (kind == "mamba"):
            raise ValueError(f"layer {j} is {kind!r}, its weights are not")
    eps, r = dm["norm_eps"], dm["residual_scale"]

    emb = params["embed"][: dm["vocab"]]
    x = emb[tokens].astype(jnp.float32) * dm["embed_scale"]

    def one_period(x, pp):
        for j, kind in enumerate(kinds):
            lp = pp[f"l{j}"]
            h = _norm(x, lp["norm1"], eps)
            mix = _mamba2 if kind == "mamba" else _attention
            x = x + r * mix(lp["mixer"], dm, h, quantize)
            f = lp["ffn"]
            h2 = _norm(x, lp["norm2"], eps)
            g = jax.nn.silu(_mm(h2, _f32(f["wi_gate"], quantize)))
            u = _mm(h2, _f32(f["wi_up"], quantize))
            x = x + r * _mm(g * u, _f32(f["wo"], quantize))
        return x, None

    x, _ = jax.lax.scan(one_period, x, params["periods"])
    x = _norm(x[:, first:], params["final_norm"], eps)
    if not dm["tie_embeddings"]:
        raise ValueError("granite_hybrid's head is the tied embedding")
    return _mm(x, _f32(emb.T, quantize)) / dm["logit_divisor"]
