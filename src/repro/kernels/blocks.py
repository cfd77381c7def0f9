"""Block-shape choice under the TPU's (8,128) tiling rule.

A Pallas TPU block's last two dimensions must each either span the whole
array dimension or be a multiple of the native tile: 128 lanes for the
last and 8 sublanes for the second-to-last. The compiler refuses any
other block, so every kernel picks its blocks here."""

from __future__ import annotations

SUBLANES = 8
LANES = 128


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def block_size(dim: int, cap: int, align: int, *, what: str = "") -> int:
    """The largest block of at most ``cap`` that tiles ``dim`` exactly and
    meets the tiling rule: ``dim`` itself when it fits under ``cap``,
    else a multiple of ``align`` that divides ``dim``."""
    if dim <= cap:
        return dim
    for b in range(cap - cap % align, 0, -align):
        if dim % b == 0:
            return b
    raise ValueError(
        f"{what or 'dimension'} {dim} has no block of at most {cap} that "
        f"divides it and is a multiple of {align}")
