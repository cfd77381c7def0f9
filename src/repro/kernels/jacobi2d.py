"""Jacobi 5-point stencil Pallas kernel (the paper's Category-II workload).

Grid over row-blocks; each step binds THREE views of the input (the block
above, the block itself, the block below) via separate BlockSpecs — the
Pallas TPU idiom for halo exchange without overlapping block shapes. Rows
are updated on the VPU; global boundary rows/cols pass through unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import SUBLANES, block_size

BLOCK_H = 256
#: bytes of VMEM for the pipelined row blocks: three input views and the
#: output, each double-buffered, so 8 blocks of (bh, C). The rest of v5e's
#: 16 MiB default scoped VMEM holds the kernel's (bh, C) f32 temporaries;
#: at 8192 columns this gives 32 f32 rows (64 bf16), which compiles for v5e.
VMEM_BLOCK_BUDGET = 8 * 2**20


def _jacobi_kernel(up_ref, mid_ref, dn_ref, out_ref, *, bh: int,
                   nrows: int, ncols: int):
    i = pl.program_id(0)
    mid = mid_ref[...].astype(jnp.float32)        # (bh, C)
    up = up_ref[...].astype(jnp.float32)          # (bh, C) block above
    dn = dn_ref[...].astype(jnp.float32)          # (bh, C) block below

    # row i-1 / i+1 within this block, with halo rows from neighbours
    above = jnp.concatenate([up[-1:], mid[:-1]], axis=0)
    below = jnp.concatenate([mid[1:], dn[:1]], axis=0)
    left = jnp.concatenate([mid[:, :1], mid[:, :-1]], axis=1)
    right = jnp.concatenate([mid[:, 1:], mid[:, -1:]], axis=1)
    res = 0.2 * (mid + above + below + left + right)

    # masks: global boundary rows/cols keep their input values
    gr = i * bh + jax.lax.broadcasted_iota(jnp.int32, (bh, ncols), 0)
    gc = jax.lax.broadcasted_iota(jnp.int32, (bh, ncols), 1)
    interior = ((gr > 0) & (gr < nrows - 1) & (gc > 0) & (gc < ncols - 1))
    out_ref[...] = jnp.where(interior, res, mid).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def jacobi2d_pallas(a: jax.Array, interpret: bool = False) -> jax.Array:
    R, C = a.shape
    cap = VMEM_BLOCK_BUDGET // (8 * C * a.dtype.itemsize)
    cap = max(SUBLANES, min(BLOCK_H, cap - cap % SUBLANES))
    # blocks must tile the rows exactly (halo correctness)
    bh = block_size(R, cap, SUBLANES, what=f"jacobi2d rows of {a.shape}")
    nb = R // bh
    kernel = functools.partial(_jacobi_kernel, bh=bh, nrows=R, ncols=C)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            # clamped neighbour blocks provide the halo rows
            pl.BlockSpec((bh, C), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((bh, C), lambda i: (i, 0)),
            pl.BlockSpec((bh, C), lambda i: (jnp.minimum(i + 1, nb - 1), 0)),
        ],
        out_specs=pl.BlockSpec((bh, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), a.dtype),
        interpret=interpret,
    )(a, a, a)
