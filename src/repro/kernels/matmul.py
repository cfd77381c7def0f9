"""Blocked SGEMM Pallas kernel — the paper's Category-III workload,
re-expressed for the TPU memory hierarchy.

The paper's SGEMM-svm-aware fix (§4.1) pins one factor device-side and
streams row panels. The TPU-native analogue: MXU-aligned (bm, bk)x(bk, bn)
tiles with the K loop innermost in the grid, the fp32 accumulator pinned in
a VMEM scratch across the K steps (the "pinned factor"), and A/B panels
streamed HBM→VMEM per step. One output tile is written once — the product
never thrashes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import LANES, block_size, round_up

BM, BN, BK = 256, 256, 512


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matmul_pallas(a: jax.Array, b: jax.Array,
                  interpret: bool = False) -> jax.Array:
    """C = A @ B; A: (M, K), B: (K, N). Dims should be 128-multiples for
    MXU alignment (smaller inputs fall back to single blocks)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn = min(BM, M), min(BN, N)
    # K blocks must tile exactly: the out-of-bounds lanes of a partial K
    # block would add unspecified values to the accumulation. A K above
    # BK that is no multiple of 128 is zero-padded, which adds nothing.
    if K > BK and K % LANES:
        pad = round_up(K, LANES) - K
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
        K += pad
    bk = block_size(K, BK, LANES, what="matmul K")
    nk = K // bk
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), nk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
