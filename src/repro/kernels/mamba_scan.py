"""Selective-scan (Mamba-1) Pallas kernel.

Grid (batch, d_inner blocks, time chunks) with time innermost: the SSM state
h (d_block, N) persists in VMEM scratch across chunk steps, so HBM traffic
is exactly the streaming of dt/B/C/x in and y out — the recurrence itself
runs at VPU rate on VMEM-resident state. Inside a chunk the timestep loop is
a `fori_loop` over VMEM rows (sequential in time, parallel over the
(d_block, N) state lanes), which matches the hardware-friendly formulation
of mamba's CUDA kernel re-thought for the TPU memory hierarchy: chunking
bounds VMEM, the sequential grid carries the state, and no (B,S,D,N) tensor
is ever materialised.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import LANES, SUBLANES, block_size, round_up

CHUNK_T = 128
BLOCK_D = 512


def _scan_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, y_ref, h_ref,
                 *, L: int):
    t0 = pl.program_id(2)

    @pl.when(t0 == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(jnp.float32)                 # (dblk, N)

    def step(i, h):
        dt_i = dt_ref[0, i].astype(jnp.float32)        # (dblk,)
        x_i = x_ref[0, i].astype(jnp.float32)          # (dblk,)
        b_i = b_ref[0, i].astype(jnp.float32)          # (N,)
        c_i = c_ref[0, i].astype(jnp.float32)          # (N,)
        a = jnp.exp(dt_i[:, None] * A)                 # (dblk, N)
        h = a * h + (dt_i * x_i)[:, None] * b_i[None, :]
        y_ref[0, i] = (h @ c_i).astype(y_ref.dtype)    # (dblk,)
        return h

    h_ref[...] = jax.lax.fori_loop(0, L, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_scan_pallas(dt: jax.Array, A: jax.Array, B: jax.Array,
                      C: jax.Array, x: jax.Array,
                      interpret: bool = False) -> jax.Array:
    """dt, x: (Bt,S,D); A: (D,N); B, C: (Bt,S,N) -> y: (Bt,S,D)."""
    Bt, S, D = x.shape
    N = A.shape[1]
    # Time chunks and channel blocks must tile exactly and meet the tiling
    # rule. Where no aligned divisor exists the input is zero-padded:
    # padded steps have dt = 0 and leave the state alone, padded channels
    # have dt = x = A = 0 and stay zero, and both are cut off the output.
    Sp = round_up(S, SUBLANES) if S > CHUNK_T else S
    Dp = round_up(D, LANES) if D > BLOCK_D else D
    if (Sp, Dp) != (S, D):
        dt = jnp.pad(dt, ((0, 0), (0, Sp - S), (0, Dp - D)))
        x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, Dp - D)))
        B = jnp.pad(B, ((0, 0), (0, Sp - S), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, Sp - S), (0, 0)))
        A = jnp.pad(A, ((0, Dp - D), (0, 0)))
    L = block_size(Sp, CHUNK_T, SUBLANES, what="mamba scan time")
    dblk = block_size(Dp, BLOCK_D, LANES, what="mamba scan channels")
    grid = (Bt, Dp // dblk, Sp // L)
    kern = functools.partial(_scan_kernel, L=L)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, dblk), lambda b, d, t: (b, t, d)),  # dt
            pl.BlockSpec((1, L, N), lambda b, d, t: (b, t, 0)),     # B
            pl.BlockSpec((1, L, N), lambda b, d, t: (b, t, 0)),     # C
            pl.BlockSpec((1, L, dblk), lambda b, d, t: (b, t, d)),  # x
            pl.BlockSpec((dblk, N), lambda b, d, t: (d, 0)),        # A
        ],
        out_specs=pl.BlockSpec((1, L, dblk), lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((Bt, Sp, Dp), x.dtype),
        scratch_shapes=[pltpu.VMEM((dblk, N), jnp.float32)],
        interpret=interpret,
    )(dt, B, C, x, A)[:, :S, :D]
