"""Model assembly: layer stacks (scan-over-periods), decoder-only LMs,
hybrid SSM/attention stacks, VLM cross-attention, and encoder-decoder.

Layers are grouped into *periods* (one repetition of `cfg.layer_pattern`);
periods are executed with `jax.lax.scan` over stacked parameters so HLO size
and compile time are independent of depth. Layers that do not fill a whole
period are unrolled at the end ("remainder"). KV/SSM caches follow the same
layout (leading n_periods axis), so prefill and decode also scan. Decode
carries the attention and Mamba-2 caches through the scan whole and writes
each layer's new KV row, or its new state, into them in place
(`decode_step`).

Prefill and decode name their parts with `jax.named_scope`, which XLA keeps
in each compiled op's ``op_name``: ``layers`` (the layer stack, and with it
the scan's carry, its slicing of the weights and, in prefill, its stacking
of the cache), ``attention``, ``ssm`` (a Mamba-2 mixer) and ``mlp`` (each
sublayer with its norm and residual add), ``kv_cache`` (inside
``attention``: the writes into the decode cache), ``ssm_state`` (inside
``ssm``: the read and in-place write of the carried state and conv ring,
in prefill the cache built from the prompt) and ``lm_head`` (final norm
and head). A profiler trace attributes device time by these names
(docs/serving.md, "Tracing the serving path").
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import mamba2 as mamba2_lib
from repro.models import moe as moe_lib
from repro.models.config import (
    ATTN,
    ATTN_LOCAL,
    CROSS,
    MAMBA,
    MAMBA2,
    MLP,
    MOE,
    NONE,
    ModelConfig,
)
from repro.models.layers import (
    dense_init,
    embed_apply,
    embed_init,
    mlp_apply,
    mlp_init,
    rms_norm,
)

Array = jax.Array
PyTree = Any

# Optional GSPMD hints, set by the launch layer before lowering:
#   LOGITS_SPEC — PartitionSpec for (B,S,V) logits (vocab over 'model')
#   ACT_SPEC    — PartitionSpec for (B,S,D) residual activations; anchors
#                 batch sharding through the embedding gather and the
#                 period-scan boundaries (GSPMD propagation can drop it at
#                 gathers — observed as 17 GB replicated score tensors).
LOGITS_SPEC = None
ACT_SPEC = None

# Roofline instrumentation: XLA cost_analysis counts while-loop bodies once,
# so the dry-run's roofline tier unrolls the period stack (at reduced depth)
# to make HLO FLOP counts exact. Never enabled for real training.
UNROLL_PERIODS = False


def _period_slice(pparams: PyTree, i: int) -> PyTree:
    return jax.tree.map(lambda x: x[i], pparams)


def _scope(name: str, on: bool = True):
    """``jax.named_scope(name)`` where ``on``, else no scope. A sublayer's
    scope holds its residual add: XLA names a projection fused with that
    add after the add."""
    return jax.named_scope(name) if on else contextlib.nullcontext()


def _mixer_scope(mixer: str):
    """The named scope of a mixer sublayer: ``attention`` or ``ssm``
    (Mamba-2); the other kinds have none."""
    if mixer in (ATTN, ATTN_LOCAL):
        return jax.named_scope("attention")
    if mixer == MAMBA2:
        return jax.named_scope("ssm")
    return contextlib.nullcontext()


def _anchor(x: Array) -> Array:
    if ACT_SPEC is not None and x.ndim == len(ACT_SPEC):
        return jax.lax.with_sharding_constraint(x, ACT_SPEC)
    return x


# ------------------------------------------------------------------- init

def _init_layer(key, cfg: ModelConfig, mixer: str, ffn: str) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"norm1": jnp.zeros((cfg.d_model,), jnp.bfloat16)}
    if mixer in (ATTN, ATTN_LOCAL):
        p["mixer"] = attn_lib.attn_init(k1, cfg)
    elif mixer == CROSS:
        p["mixer"] = attn_lib.attn_init(k1, cfg)
        p["gate"] = jnp.zeros((), jnp.bfloat16)   # gated cross (llama-vision)
    elif mixer == MAMBA:
        p["mixer"] = mamba_lib.mamba_init(k1, cfg)
    elif mixer == MAMBA2:
        p["mixer"] = mamba2_lib.mamba2_init(k1, cfg)
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if ffn == MLP:
        p["norm2"] = jnp.zeros((cfg.d_model,), jnp.bfloat16)
        p["ffn"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_gated)
    elif ffn == MOE:
        p["norm2"] = jnp.zeros((cfg.d_model,), jnp.bfloat16)
        p["ffn"] = moe_lib.moe_init(k2, cfg)
    elif ffn != NONE:
        raise ValueError(f"unknown ffn {ffn!r}")
    return p


def _init_period(key, cfg: ModelConfig) -> dict:
    pat, fpat = cfg.layer_pattern, cfg.ffn_pattern
    keys = jax.random.split(key, len(pat))
    return {
        f"l{j}": _init_layer(keys[j], cfg, pat[j], fpat[j % len(fpat)])
        for j in range(len(pat))
    }


def init_params(cfg: ModelConfig, key) -> PyTree:
    if len(cfg.layer_pattern) % len(cfg.ffn_pattern) != 0 \
            and len(cfg.ffn_pattern) % len(cfg.layer_pattern) != 0:
        raise ValueError("ffn_pattern must align with layer_pattern periods")
    k_embed, k_per, k_rem, k_head, k_enc = jax.random.split(key, 5)
    params: dict = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.bfloat16),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head,
                                       (cfg.d_model, cfg.padded_vocab))
    if cfg.n_periods > 0:
        pkeys = jax.random.split(k_per, cfg.n_periods)
        params["periods"] = jax.vmap(
            lambda k: _init_period(k, cfg))(pkeys)
    if cfg.n_remainder > 0:
        rkeys = jax.random.split(k_rem, cfg.n_remainder)
        base = cfg.n_periods * len(cfg.layer_pattern)
        params["remainder"] = {
            f"r{i}": _init_layer(
                rkeys[i], cfg,
                cfg.layer_pattern[(base + i) % len(cfg.layer_pattern)],
                cfg.ffn_pattern[(base + i) % len(cfg.ffn_pattern)])
            for i in range(cfg.n_remainder)
        }
    if cfg.is_encdec:
        ekeys = jax.random.split(k_enc, cfg.encoder_layers + 1)
        params["encoder"] = {
            f"e{i}": {
                "norm1": jnp.zeros((cfg.d_model,), jnp.bfloat16),
                "mixer": attn_lib.attn_init(ekeys[i], cfg),
                "norm2": jnp.zeros((cfg.d_model,), jnp.bfloat16),
                "ffn": mlp_init(jax.random.fold_in(ekeys[i], 1),
                                cfg.d_model, cfg.d_ff, cfg.mlp_gated),
            }
            for i in range(cfg.encoder_layers)
        }
        params["encoder"]["final_norm"] = jnp.zeros((cfg.d_model,),
                                                    jnp.bfloat16)
    return params


# ------------------------------------------------------------------ layers

def _theta_for(cfg: ModelConfig, mixer: str) -> float:
    if mixer == ATTN and cfg.rope_theta_global:
        return cfg.rope_theta_global
    return cfg.rope_theta


def _apply_layer(
    lp: dict,
    cfg: ModelConfig,
    x: Array,
    mixer: str,
    ffn: str,
    *,
    positions: Array,
    ctx: Optional[Array],
    cache: Optional[dict],
    decode: bool,
    layer: tuple = (),
) -> tuple[Array, Optional[dict], Array]:
    """One residual layer. Returns (x, cache_out, moe_aux). An attention or
    Mamba-2 layer's decode cache may be stacked: ``layer`` indexes its
    leading axes, and the whole stack comes back with this layer's row (or
    state) written."""
    cache_out: Optional[dict] = None
    attn = mixer in (ATTN, ATTN_LOCAL)
    with _mixer_scope(mixer):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        if attn:
            window = cfg.sliding_window if mixer == ATTN_LOCAL else 0
            o, kv = attn_lib.self_attention(
                lp["mixer"], cfg, h, positions=positions, window=window,
                theta=_theta_for(cfg, mixer),
                cache=cache if decode else None, layer=layer)
            cache_out = kv
        elif mixer == CROSS:
            o = attn_lib.cross_attention(lp["mixer"], cfg, h, ctx)
            o = o * jnp.tanh(lp["gate"].astype(jnp.float32)).astype(o.dtype) \
                if "gate" in lp else o
            cache_out = {}
        elif mixer == MAMBA:
            if decode:
                o, cache_out = mamba_lib.mamba_decode_step(lp["mixer"], cfg,
                                                           h, cache)
            else:
                o = mamba_lib.mamba_forward(lp["mixer"], cfg, h)
                cache_out = None  # prefill state handled separately
        elif mixer == MAMBA2:
            if decode:
                o, cache_out = mamba2_lib.mamba2_decode_step(
                    lp["mixer"], cfg, h, cache, layer=layer)
            else:
                o = mamba2_lib.mamba2_forward(lp["mixer"], cfg, h)
        else:
            raise ValueError(mixer)
        x = x + o
    aux = jnp.zeros((), jnp.float32)
    if ffn in (MLP, MOE):
        with _scope("mlp", ffn == MLP):
            h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
            if ffn == MLP:
                f = mlp_apply(lp["ffn"], h2, cfg.act)
            else:
                f, aux = moe_lib.moe_apply(lp["ffn"], cfg, h2)
            x = x + f
    return x, cache_out, aux


def _kind(cfg: ModelConfig, j: int) -> tuple[str, str]:
    return (cfg.layer_pattern[j % len(cfg.layer_pattern)],
            cfg.ffn_pattern[j % len(cfg.ffn_pattern)])


# --------------------------------------------------------------- forward

def forward(params: PyTree, cfg: ModelConfig, tokens: Array,
            ctx: Optional[Array] = None,
            return_hidden: bool = False) -> tuple[Array, Array]:
    """Teacher-forced full-sequence pass. Returns (logits, moe_aux_mean);
    with return_hidden=True returns the final normed hidden states instead
    of logits (the train loss folds the LM head into a chunked CE)."""
    B, S = tokens.shape
    x = embed_apply(params["embed"], tokens, cfg.embed_scale, cfg.d_model)
    x = _anchor(x)
    # batch-free positions: masks stay (1,1,1,S,T), not per-batch-element
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]

    def period_body(carry, pparams):
        xc, aux = carry
        for j, mixer in enumerate(cfg.layer_pattern):
            _, fkind = _kind(cfg, j)
            xc, _, a = _apply_layer(
                pparams[f"l{j}"], cfg, xc, mixer, fkind,
                positions=positions, ctx=ctx, cache=None, decode=False)
            aux = aux + a
        return (_anchor(xc), aux), None

    if cfg.remat == "full":
        period_body = jax.checkpoint(period_body, prevent_cse=False)

    aux = jnp.zeros((), jnp.float32)
    if cfg.n_periods > 0:
        if UNROLL_PERIODS:
            for i in range(cfg.n_periods):
                (x, aux), _ = period_body(
                    (x, aux), _period_slice(params["periods"], i))
        else:
            (x, aux), _ = jax.lax.scan(period_body, (x, aux),
                                       params["periods"])
    base = cfg.n_periods * len(cfg.layer_pattern)
    for i in range(cfg.n_remainder):
        mixer, fkind = _kind(cfg, base + i)
        x, _, a = _apply_layer(
            params["remainder"][f"r{i}"], cfg, x, mixer, fkind,
            positions=positions, ctx=ctx, cache=None, decode=False)
        aux = aux + a
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    n_moe = max(1, sum(1 for _, f in cfg.layer_kinds() if f == MOE))
    if return_hidden:
        return x, aux / n_moe
    logits = _lm_head(params, cfg, x)
    return logits, aux / n_moe


def _lm_head(params: PyTree, cfg: ModelConfig, x: Array) -> Array:
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    if LOGITS_SPEC is not None:
        logits = jax.lax.with_sharding_constraint(logits, LOGITS_SPEC)
    if cfg.padded_vocab != cfg.vocab:  # mask the padded vocab tail
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.asarray(-2.0e38, logits.dtype),
                           logits)
    return logits


def encode(params: PyTree, cfg: ModelConfig, frames: Array) -> Array:
    """Encoder stack over precomputed modality-frontend frames (enc-dec)."""
    x = frames
    enc = params["encoder"]
    for i in range(cfg.encoder_layers):
        lp = enc[f"e{i}"]
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        x = x + attn_lib.encoder_self_attention(lp["mixer"], cfg, h)
        h2 = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + mlp_apply(lp["ffn"], h2, cfg.act)
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


# ----------------------------------------------------------------- caches

def _layer_cache(cfg: ModelConfig, mixer: str, B: int, S: int) -> dict:
    hd = cfg.resolved_head_dim
    if mixer in (ATTN, ATTN_LOCAL):
        W = S if (mixer == ATTN or not cfg.sliding_window) \
            else min(cfg.sliding_window, S)
        return {
            "k": jnp.zeros((B, W, cfg.n_kv_heads * hd), jnp.bfloat16),
            "v": jnp.zeros((B, W, cfg.n_kv_heads * hd), jnp.bfloat16),
            "pos": jnp.full((B, W), -1, jnp.int32),
        }
    if mixer == MAMBA:
        return mamba_lib.mamba_init_cache(cfg, B)
    if mixer == MAMBA2:
        return mamba2_lib.mamba2_init_cache(cfg, B)
    if mixer == CROSS:
        return {}
    raise ValueError(mixer)


def init_cache(cfg: ModelConfig, B: int, S: int) -> PyTree:
    """Decode cache sized for a context of S tokens."""
    cache: dict = {"t": jnp.zeros((B,), jnp.int32)}
    if cfg.n_periods > 0:
        def one_period(_):
            return {f"l{j}": _layer_cache(cfg, cfg.layer_pattern[j], B, S)
                    for j in range(len(cfg.layer_pattern))}
        cache["periods"] = jax.tree.map(
            lambda leaf: jnp.broadcast_to(
                leaf, (cfg.n_periods,) + leaf.shape).copy(),
            one_period(None))
    base = cfg.n_periods * len(cfg.layer_pattern)
    if cfg.n_remainder > 0:
        cache["remainder"] = {
            f"r{i}": _layer_cache(
                cfg, cfg.layer_pattern[(base + i) % len(cfg.layer_pattern)],
                B, S)
            for i in range(cfg.n_remainder)
        }
    return cache


# ---------------------------------------------------------------- prefill

def _kv_to_buffer(kv: dict, W: int) -> dict:
    """Convert full-sequence K/V (B,S,KV,hd) into the rolling decode buffer
    layout (B,W,KV*hd) + per-slot absolute positions."""
    k, v, pos = kv["k"], kv["v"], kv["pos"]
    B, S, KV, hd = k.shape
    take = min(W, S)
    slots = (jnp.arange(S - take, S, dtype=jnp.int32) % W)    # (take,)

    def buf(x):
        tail = x[:, S - take:].reshape(B, take, KV * hd)
        return jnp.zeros((B, W, KV * hd), x.dtype).at[:, slots].set(tail)

    bpos = jnp.full((B, W), -1, jnp.int32).at[:, slots].set(pos[:, S - take:])
    return {"k": buf(k), "v": buf(v), "pos": bpos}


def prefill(params: PyTree, cfg: ModelConfig, tokens: Array,
            ctx: Optional[Array] = None, cache_len: int | None = None
            ) -> tuple[Array, PyTree]:
    """Process a prompt, returning (logits, decode cache)."""
    B, S = tokens.shape
    CL = cache_len or S
    x = embed_apply(params["embed"], tokens, cfg.embed_scale, cfg.d_model)
    x = _anchor(x)
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]

    def run_layer(lp, xc, mixer, fkind):
        attn = mixer in (ATTN, ATTN_LOCAL)
        with _mixer_scope(mixer):
            h = rms_norm(xc, lp["norm1"], cfg.norm_eps)
            if attn:
                window = cfg.sliding_window if mixer == ATTN_LOCAL else 0
                o, kv = attn_lib.self_attention(
                    lp["mixer"], cfg, h, positions=positions, window=window,
                    theta=_theta_for(cfg, mixer))
                W = CL if (mixer == ATTN or not cfg.sliding_window) \
                    else min(cfg.sliding_window, CL)
                with jax.named_scope("kv_cache"):
                    c_out = _kv_to_buffer(kv, W)
            elif mixer == CROSS:
                o = attn_lib.cross_attention(lp["mixer"], cfg, h, ctx)
                o = o * jnp.tanh(
                    lp["gate"].astype(jnp.float32)).astype(o.dtype)
                c_out = {}
            elif mixer == MAMBA:
                o, c_out = mamba_lib.mamba_forward(lp["mixer"], cfg, h,
                                                   return_state=True)
            elif mixer == MAMBA2:
                o, c_out = mamba2_lib.mamba2_forward(lp["mixer"], cfg, h,
                                                     return_state=True)
            else:
                raise ValueError(mixer)
            xc = xc + o
        if fkind in (MLP, MOE):
            with _scope("mlp", fkind == MLP):
                h2 = rms_norm(xc, lp["norm2"], cfg.norm_eps)
                f = (mlp_apply(lp["ffn"], h2, cfg.act) if fkind == MLP
                     else moe_lib.moe_apply(lp["ffn"], cfg, h2)[0])
                xc = xc + f
        return xc, c_out

    cache: dict = {"t": jnp.full((B,), S, jnp.int32)}

    def period_body(xc, pparams):
        outs = {}
        for j, mixer in enumerate(cfg.layer_pattern):
            _, fkind = _kind(cfg, j)
            xc, outs[f"l{j}"] = run_layer(pparams[f"l{j}"], xc, mixer, fkind)
        return xc, outs

    with jax.named_scope("layers"):
        if cfg.n_periods > 0:
            if UNROLL_PERIODS:
                outs = []
                for i in range(cfg.n_periods):
                    x, o = period_body(x,
                                       _period_slice(params["periods"], i))
                    outs.append(o)
                cache["periods"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *outs)
            else:
                x, cache["periods"] = jax.lax.scan(period_body, x,
                                                   params["periods"])
        base = cfg.n_periods * len(cfg.layer_pattern)
        if cfg.n_remainder > 0:
            cache["remainder"] = {}
            for i in range(cfg.n_remainder):
                mixer, fkind = _kind(cfg, base + i)
                x, c_out = run_layer(params["remainder"][f"r{i}"], x, mixer,
                                     fkind)
                cache["remainder"][f"r{i}"] = c_out
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head(params, cfg, x)
    return logits, cache


# ----------------------------------------------------------------- decode

def decode_step(params: PyTree, cfg: ModelConfig, token: Array,
                cache: PyTree, ctx: Optional[Array] = None
                ) -> tuple[Array, PyTree]:
    """One greedy decode step. token: (B, 1) int32.

    The attention and Mamba-2 layers' stacked caches are loop state: the
    period loop carries them whole, and each layer writes one row of K, V
    and position per batch element (`attention.self_attention`), or its
    new SSM state and conv ring (`mamba2.mamba2_decode_step`), into them in
    place at period i; no layer's buffer and no stack is copied. The other
    layer kinds (Mamba-1 state, cross-attention's empty cache) are small
    and are handed from period to period as scanned inputs and outputs."""
    x = embed_apply(params["embed"], token, cfg.embed_scale, cfg.d_model)
    positions = cache["t"][:, None]                            # (B,1)
    new_cache: dict = {"t": cache["t"] + 1}
    inplace = {f"l{j}" for j, m in enumerate(cfg.layer_pattern)
               if m in (ATTN, ATTN_LOCAL, MAMBA2)}

    def period_body(xc, carried, pparams, pstate, i):
        """Period i: ``carried`` holds the stacked attention and Mamba-2
        caches (written at row i), ``pstate`` the other layers' caches of
        this period."""
        carried, outs = dict(carried), {}
        for j, mixer in enumerate(cfg.layer_pattern):
            _, fkind = _kind(cfg, j)
            name = f"l{j}"
            if name in inplace:
                xc, carried[name], _ = _apply_layer(
                    pparams[name], cfg, xc, mixer, fkind,
                    positions=positions, ctx=ctx, cache=carried[name],
                    decode=True, layer=(i,))
            else:
                xc, outs[name], _ = _apply_layer(
                    pparams[name], cfg, xc, mixer, fkind,
                    positions=positions, ctx=ctx, cache=pstate[name],
                    decode=True)
        return xc, carried, outs

    with jax.named_scope("layers"):
        if cfg.n_periods > 0:
            carried = {n: c for n, c in cache["periods"].items()
                       if n in inplace}
            rest = {n: c for n, c in cache["periods"].items()
                    if n not in inplace}
            if UNROLL_PERIODS:
                outs = []
                for i in range(cfg.n_periods):
                    x, carried, o = period_body(
                        x, carried, _period_slice(params["periods"], i),
                        _period_slice(rest, i), i)
                    outs.append(o)
                rest = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
            else:
                def scan_body(carry, scanned):
                    xc, cc = carry
                    xc, cc, o = period_body(xc, cc, *scanned)
                    return (xc, cc), o

                (x, carried), rest = jax.lax.scan(
                    scan_body, (x, carried),
                    (params["periods"], rest,
                     jnp.arange(cfg.n_periods, dtype=jnp.int32)))
            new_cache["periods"] = {**carried, **rest}
        base = cfg.n_periods * len(cfg.layer_pattern)
        if cfg.n_remainder > 0:
            new_cache["remainder"] = {}
            for i in range(cfg.n_remainder):
                mixer, fkind = _kind(cfg, base + i)
                x, new_cache["remainder"][f"r{i}"], _ = _apply_layer(
                    params["remainder"][f"r{i}"], cfg, x, mixer, fkind,
                    positions=positions, ctx=ctx,
                    cache=cache["remainder"][f"r{i}"], decode=True)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head(params, cfg, x)
    return logits, new_cache
