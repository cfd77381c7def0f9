"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU, asserting output shapes and absence of NaNs. The FULL configs
are exercised only via the dry-run (ShapeDtypeStruct, no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.models import decode_step, encode, forward, init_cache, init_params, prefill

B, S = 2, 16


def _ctx_for(cfg, key, B):
    if cfg.is_encdec:
        frames = jax.random.normal(
            key, (B, cfg.encoder_frames, cfg.d_model)).astype(jnp.bfloat16)
        return frames, "frames"
    if cfg.is_vlm:
        img = jax.random.normal(
            key, (B, cfg.image_tokens, cfg.d_model)).astype(jnp.bfloat16)
        return img, "image"
    return None, None


def _run_forward(cfg):
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    ctx, kind = _ctx_for(cfg, jax.random.PRNGKey(2), B)
    if kind == "frames":
        ctx = encode(params, cfg, ctx)
    logits, aux = forward(params, cfg, tokens, ctx=ctx)
    return params, tokens, ctx, logits, aux


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_reduced(arch)
    params, tokens, ctx, logits, aux = _run_forward(cfg)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_updates_and_finite(arch):
    cfg = get_reduced(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    ctx, kind = _ctx_for(cfg, jax.random.PRNGKey(2), B)

    def loss_fn(p):
        c = encode(p, cfg, ctx) if kind == "frames" else ctx
        logits, aux = forward(p, cfg, tokens, ctx=c)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)
        return -jnp.mean(ll) + 0.01 * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    gnorm = jax.tree.reduce(
        lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))),
        grads, 0.0)
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0.0
    new = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                       params, grads)
    loss2 = loss_fn(new)
    assert bool(jnp.isfinite(loss2))


DECODE_ARCHS = ["granite-3-2b", "gemma3-1b", "falcon-mamba-7b",
                "mixtral-8x7b", "jamba-1.5-large-398b", "chatglm3-6b",
                "granite-4.0-h-micro"]
N_DECODE = 6


def _close(a, b):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=2e-2, atol=2e-2)


def _prefill_decode(cfg, params, tokens):
    """Prefill all but the last N_DECODE tokens into a cache as wide as
    the whole sequence, then decode those one step at a time. Returns
    (prefill's last logits, each decode step's logits)."""
    pre_logits, cache = prefill(params, cfg, tokens[:, : S - N_DECODE],
                                cache_len=S)
    step = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c))
    steps = []
    for i in range(S - N_DECODE, S):
        logits, cache = step(params, tokens[:, i: i + 1], cache)
        steps.append(logits[:, 0])
    return pre_logits[:, -1], steps


@pytest.mark.parametrize("arch,unrolled", [
    *(pytest.param(a, False, id=a) for a in DECODE_ARCHS),
    *(pytest.param(a, True, id=f"{a}-unrolled") for a in DECODE_ARCHS)])
def test_prefill_decode_matches_forward(arch, unrolled, monkeypatch):
    """Teacher-forced forward and prefill + N_DECODE decode steps must
    produce the same next-token logits at every step. This validates the
    cache: rows written in place into the stacked buffer, rolling
    sliding-window buffers past their width (gemma3's local layers hold 8
    of 16 positions), partial rotary over 2 KV groups (chatglm3), Mamba-1
    state hand-off and Mamba-2 state written in place after a prefill over
    two chunks (granite-4.0-h-micro). MoE capacity is raised so no tokens
    drop — capacity-based routing otherwise drops *different* tokens for
    different total token counts, which is expected behaviour, not a cache
    bug.
    Unrolled, the period stack runs as a Python loop (`UNROLL_PERIODS`),
    and its decode must give the scanned decode's logits (up to bf16
    rounding: XLA fuses the two programs differently)."""
    import dataclasses
    from repro.models import transformer
    cfg = dataclasses.replace(get_reduced(arch), capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)

    if unrolled:
        _, scanned = _prefill_decode(cfg, params, tokens)
        monkeypatch.setattr(transformer, "UNROLL_PERIODS", True)
    full_logits, _ = forward(params, cfg, tokens)
    pre_last, steps = _prefill_decode(cfg, params, tokens)
    _close(pre_last, full_logits[:, S - N_DECODE - 1])
    for i, logits in enumerate(steps):
        _close(logits, full_logits[:, S - N_DECODE + i])
    if unrolled:
        for a, b in zip(steps, scanned):
            _close(a, b)


def test_param_count_matches_analytic():
    """Analytic 6ND accounting must match the real parameter tree."""
    for arch in ("granite-3-2b", "falcon-mamba-7b", "mixtral-8x7b",
                 "granite-4.0-h-micro"):
        cfg = get_reduced(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(l.shape))
                     for l in jax.tree.leaves(params))
        assert actual == cfg.param_count(), arch


def test_full_configs_param_counts():
    """Sanity: full configs land near their nominal sizes (no allocation —
    analytic count only)."""
    expect = {
        "gemma3-1b": (0.9e9, 1.6e9),
        "granite-3-2b": (2.0e9, 3.0e9),
        "chatglm3-6b": (5.5e9, 7.0e9),
        "granite-20b": (18e9, 22e9),
        "mixtral-8x7b": (44e9, 49e9),
        "granite-moe-1b-a400m": (1.0e9, 1.6e9),
        "jamba-1.5-large-398b": (370e9, 420e9),
        "falcon-mamba-7b": (6.5e9, 8.0e9),
        "llama-3.2-vision-11b": (9e9, 12e9),
        "seamless-m4t-medium": (0.55e9, 1.2e9),
        "granite-4.0-h-micro": (3.1e9, 3.3e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"
