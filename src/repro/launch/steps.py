"""Train and serve step factories: loss, microbatched grad accumulation,
ZeRO-sharded optimizer update, greedy decode."""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import decode_step, encode, forward
from repro.models.config import ModelConfig
from repro.optim import OptConfig, clip_by_global_norm, make_optimizer

PyTree = Any


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """CE via logsumexp — never materialises log-probs over the (possibly
    vocab-sharded) logits; only (B,S) reductions leave the shard."""
    lg = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    lab = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - lab)


CE_CHUNK = 512


def chunked_cross_entropy(x: jax.Array, head: jax.Array, labels: jax.Array,
                          transpose_head: bool,
                          vocab: int | None = None) -> jax.Array:
    """Fused LM-head + CE, scanned over sequence chunks: the (B,S,V) logits
    tensor never exists — each chunk computes its (B,C,V) logits, reduces to
    logsumexp/label-logit scalars, and is rematerialised in the backward.
    This is the production memory-safe CE (vocab up to 262k at S=4k/32k)."""
    B, S, D = x.shape
    C = min(CE_CHUNK, S)
    pad = (-S) % C
    nc = (S + pad) // C
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    xs = jnp.moveaxis(xs.reshape(B, nc, C, D), 1, 0)          # (nc,B,C,D)
    ls = jnp.pad(labels, ((0, 0), (0, pad)))
    ls = jnp.moveaxis(ls.reshape(B, nc, C), 1, 0)             # (nc,B,C)
    valid = jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
    vs = jnp.moveaxis(valid.reshape(B, nc, C), 1, 0)

    V = head.shape[0] if transpose_head else head.shape[-1]
    pad_mask = (jnp.arange(V) >= vocab) if (vocab and vocab != V) else None

    def body(acc, inp):
        x_c, l_c, v_c = inp
        logits = (x_c @ head.T if transpose_head else x_c @ head)
        lg = logits.astype(jnp.float32)
        if pad_mask is not None:  # padded vocab tail never scores
            lg = jnp.where(pad_mask, -2.0e38, lg)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        lab = jnp.take_along_axis(lg, l_c[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((lse - lab) * v_c), None

    body = jax.checkpoint(body, prevent_cse=False)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (xs, ls, vs))
    return total / (B * S)


def loss_fn(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, ctx: Optional[jax.Array]) -> jax.Array:
    c = encode(params, cfg, ctx) if cfg.is_encdec else ctx
    x, aux = forward(params, cfg, tokens, ctx=c, return_hidden=True)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    ce = chunked_cross_entropy(x, head, labels, cfg.tie_embeddings,
                               vocab=cfg.vocab)
    return ce + 0.01 * aux


def make_train_step(cfg: ModelConfig, opt_cfg: OptConfig,
                    microbatches: int = 1):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). batch = {"tokens","labels"[,"ctx"]} with a global batch dim
    that microbatching splits on-device (grad accumulation via lax.scan)."""
    _, opt_update = make_optimizer(opt_cfg)

    def grads_of(params, tokens, labels, ctx):
        return jax.value_and_grad(loss_fn)(params, cfg, tokens, labels, ctx)

    def train_step(params, opt_state, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        ctx = batch.get("ctx")
        if microbatches == 1:
            loss, grads = grads_of(params, tokens, labels, ctx)
        else:
            B = tokens.shape[0]
            mb = B // microbatches

            def split(x):
                return x.reshape(microbatches, mb, *x.shape[1:])

            mtok, mlab = split(tokens), split(labels)
            mctx = split(ctx) if ctx is not None else None

            def body(acc, inp):
                g_acc, l_acc = acc
                t, l, c = inp
                loss_i, g_i = grads_of(params, t, l, c)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), g_acc, g_i)
                return (g_acc, l_acc + loss_i), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16)
                              if p.dtype == jnp.bfloat16
                              else jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)),
                (mtok, mlab, mctx))
            scale = 1.0 / microbatches
            grads = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                grads)
            loss = loss * scale
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.grad_clip)
        params, opt_state = opt_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_serve_step(cfg: ModelConfig):
    """Returns serve_step(params, token, cache[, ctx]) -> (next_ids, logits,
    cache): one greedy decode step over a seq_len-deep KV/SSM cache.
    ``logits`` (B, V) are the step's own; serving samples ``next_ids`` from
    them, and a correctness check compares them against `forward`."""

    def serve_step(params, token, cache, ctx=None):
        logits, cache = decode_step(params, cfg, token, cache, ctx=ctx)
        with jax.named_scope("lm_head"):
            last = logits[:, -1]
            next_ids = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return next_ids[:, None], last, cache

    return serve_step


def make_prefill_step(cfg: ModelConfig, cache_len: int | None = None):
    """Returns prefill_step(params, tokens[, ctx]) -> (next_ids, last_logits,
    cache). Only the final position's logits are returned — serving samples
    from them, and a full (B,S,V) logits output would dominate the step's
    output bytes (537 GB for a 256k vocab at 32k prefill).

    ``cache_len`` is the decode cache's width: the prompt plus every token
    that will be decoded into it. It defaults to the prompt length, which
    leaves no free slot: the first decoded token then overwrites the
    prompt's oldest position."""
    from repro.models import prefill

    def prefill_step(params, tokens, ctx=None):
        c = encode(params, cfg, ctx) if cfg.is_encdec else ctx
        logits, cache = prefill(params, cfg, tokens, ctx=c,
                                cache_len=cache_len)
        with jax.named_scope("lm_head"):
            last = logits[:, -1]
            next_ids = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return next_ids[:, None], last, cache

    return prefill_step
