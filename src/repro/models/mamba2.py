"""Mamba-2 mixer (state-space duality, SSD), as granite-4.0-h runs it.

Per token t and head h, with ``in_proj`` split as [z (di), xBC (di + 2·G·N),
dt (H)] and head h reading group g = h // (H / G) of B and C:

    xBC  = silu(causal_conv(xBC) + conv_b) = [x (H×P), B (G×N), C (G×N)]
    dt_h = softplus(dt_h + dt_bias_h),    A_h = -exp(A_log_h)
    S_h <- exp(dt_h·A_h)·S_h + dt_h·x_h ⊗ B_g      (state P×N per head)
    y_h  = S_h·C_g + D_h·x_h
    out  = out_proj(RMSNorm(y ⊙ silu(z)))          (over all di channels)

The norm's scale is stored as an offset from one, as every norm here.

Prefill (`mamba2_forward`) runs the chunked SSD form: inside a chunk of
``cfg.ssm_chunk`` tokens the decay-masked C·Bᵀ products, across chunks the
carried state. It returns each sequence's final state and the conv's last
K-1 inputs. Decode (`mamba2_decode_step`) is one recurrence step on that
cache. The conv, the recurrence and the state are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rms_norm
from repro.models.mamba import _causal_conv

Array = jax.Array
F32 = jnp.float32


def mamba2_init(key, cfg: ModelConfig) -> dict:
    d, di, h = cfg.d_model, cfg.ssm_inner, cfg.ssm_heads
    c = cfg.ssm_conv_dim
    ks = jax.random.split(key, 3)
    return {
        "in_proj": dense_init(ks[0], (d, di + c + h)),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, c), scale=0.1),
        "conv_b": jnp.zeros((c,), jnp.bfloat16),
        "dt_bias": jnp.full((h,), -4.6, F32),       # softplus^-1(0.01)
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=F32)),
        "D": jnp.ones((h,), F32),
        "norm": jnp.zeros((di,), jnp.bfloat16),
        "out_proj": dense_init(ks[2], (di, d)),
    }


def mamba2_init_cache(cfg: ModelConfig, B: int) -> dict:
    return {
        "h": jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                       F32),
        "conv": jnp.zeros((B, cfg.ssm_conv - 1, cfg.ssm_conv_dim),
                          jnp.bfloat16),
    }


def _split(cfg: ModelConfig, zxbcdt: Array):
    """in_proj's output -> (z, xBC before the conv, dt before softplus)."""
    di, c = cfg.ssm_inner, cfg.ssm_conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + c], zxbcdt[..., di + c:]


def _heads(cfg: ModelConfig, xbc: Array):
    """xBC after the conv (float32) -> x (..., G, H/G, P), B and C
    (..., G, N)."""
    di, G, N = cfg.ssm_inner, cfg.ssm_groups, cfg.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, G, cfg.ssm_heads // G,
                              cfg.ssm_head_dim)
    Bm = xbc[..., di:di + G * N].reshape(*lead, G, N)
    Cm = xbc[..., di + G * N:].reshape(*lead, G, N)
    return x, Bm, Cm


def _dt_and_decay(p: dict, cfg: ModelConfig, dt: Array):
    """dt after softplus and A, both grouped as the heads are: (..., G, H/G)
    and (G, H/G)."""
    G = cfg.ssm_groups
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    return (dt.reshape(*dt.shape[:-1], G, cfg.ssm_heads // G),
            A.reshape(G, cfg.ssm_heads // G))


def _out(p: dict, cfg: ModelConfig, y: Array, z: Array) -> Array:
    """Gated RMSNorm over every channel, then the output projection.
    y: (..., G, H/G, P) float32; z: (..., di)."""
    y = y.reshape(*z.shape)
    y = rms_norm(y * jax.nn.silu(z.astype(F32)), p["norm"], cfg.norm_eps)
    return y.astype(z.dtype) @ p["out_proj"]


def _chunk(h: Array, inp, A: Array):
    """One SSD chunk. h: (b, G, Hg, P, N) carried state; x (b, L, G, Hg, P),
    B and C (b, L, G, N), dt (b, L, G, Hg). Returns (state after the
    chunk, y (b, L, G, Hg, P) without the D skip)."""
    x, Bm, Cm, dt = inp
    L = x.shape[1]
    acum = jnp.cumsum(dt * A, axis=1)                       # (b,L,G,Hg)
    # decay from token s to token l >= s: exp(acum_l - acum_s)
    causal = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])
    seg = acum[:, :, None] - acum[:, None, :]               # (b,l,s,G,Hg)
    decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg, -jnp.inf))
    cb = jnp.einsum("blgn,bsgn->blsg", Cm, Bm)
    dtx = x * dt[..., None]                                 # (b,s,G,Hg,P)
    y = jnp.einsum("blsgh,bsghp->blghp", cb[..., None] * decay, dtx)
    # the state carried in from the chunks before, decayed to token l
    y = y + jnp.einsum("blgn,bghpn->blghp", Cm, h) * jnp.exp(acum)[..., None]
    to_end = jnp.exp(acum[:, -1:] - acum)                   # (b,s,G,Hg)
    h = (jnp.exp(acum[:, -1])[..., None, None] * h
         + jnp.einsum("bsgn,bsghp->bghpn", Bm, dtx * to_end[..., None]))
    return h, y


def mamba2_forward(p: dict, cfg: ModelConfig, x: Array,
                   return_state: bool = False):
    """Full-sequence (train / prefill) pass. x: (b, S, d) -> (b, S, d).
    With return_state=True also returns the decode cache: the final state
    ``h`` (b, H, P, N) and the conv's last K-1 inputs ``conv``."""
    b, S, _ = x.shape
    G, N, K = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
    z, xbc_in, dt = _split(cfg, x @ p["in_proj"])
    xbc = jax.nn.silu(_causal_conv(xbc_in.astype(F32),
                                   p["conv_w"].astype(F32),
                                   p["conv_b"].astype(F32)))
    xs, Bm, Cm = _heads(cfg, xbc)
    dt, A = _dt_and_decay(p, cfg, dt)

    L = min(cfg.ssm_chunk, S)
    pad = (-S) % L
    nc = (S + pad) // L

    def chunked(t):
        # padded tokens take dt = 0 (and x = B = 0): no decay, no input,
        # so the state passes them unchanged
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(b, nc, L, *t.shape[2:]), 1, 0)

    h0 = jnp.zeros((b, G, cfg.ssm_heads // G, cfg.ssm_head_dim, N), F32)
    h, ys = jax.lax.scan(lambda h, inp: _chunk(h, inp, A), h0,
                         (chunked(xs), chunked(Bm), chunked(Cm),
                          chunked(dt)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * L, *ys.shape[3:])[:, :S]
    y = y + p["D"].reshape(A.shape)[..., None] * xs
    out = _out(p, cfg, y, z)
    if not return_state:
        return out
    with jax.named_scope("ssm_state"):
        tail = jnp.pad(xbc_in, ((0, 0), (max(K - 1 - S, 0), 0), (0, 0)))
        return out, {"h": h.reshape(b, cfg.ssm_heads, cfg.ssm_head_dim, N),
                     "conv": tail[:, tail.shape[1] - (K - 1):]}


def mamba2_decode_step(p: dict, cfg: ModelConfig, x: Array, cache: dict,
                       layer: tuple = ()) -> tuple[Array, dict]:
    """One token. x: (b, 1, d). ``cache["h"]`` (..., b, H, P, N) and
    ``cache["conv"]`` (..., b, K-1, C) may be stacked: ``layer`` indexes
    their leading axes, and the whole buffers come back with this layer's
    slice overwritten in place. The output is read from the old state
    (exp(dt·A)·S·C plus the new input's part), so the new state is
    written once and never read back in the step."""
    b = x.shape[0]
    G, Hg = cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups
    z, xbc_in, dt = _split(cfg, x[:, 0] @ p["in_proj"])
    with jax.named_scope("ssm_state"):
        h = cache["h"][layer].reshape(b, G, Hg, cfg.ssm_head_dim,
                                      cfg.ssm_state)
        win = jnp.concatenate([cache["conv"][layer], xbc_in[:, None]],
                              axis=1)                        # (b, K, C)
    xbc = jax.nn.silu(jnp.sum(win.astype(F32) * p["conv_w"].astype(F32),
                              axis=1) + p["conv_b"].astype(F32))
    xs, Bm, Cm = _heads(cfg, xbc)             # (b,G,Hg,P), (b,G,N), (b,G,N)
    dt, A = _dt_and_decay(p, cfg, dt)         # (b,G,Hg)
    dA = jnp.exp(dt * A)
    dtx = xs * dt[..., None]
    y = (dA[..., None] * jnp.einsum("bghpn,bgn->bghp", h, Cm)
         + dtx * jnp.sum(Bm * Cm, axis=-1)[:, :, None, None]
         + p["D"].reshape(G, Hg)[..., None] * xs)
    with jax.named_scope("ssm_state"):
        h = dA[..., None, None] * h + dtx[..., None] * Bm[:, :, None, None]
        new = {"h": cache["h"].at[layer].set(
                   h.reshape(cache["h"].shape[len(layer):])),
               "conv": cache["conv"].at[layer].set(win[:, 1:])}
    return _out(p, cfg, y, z)[:, None], new
