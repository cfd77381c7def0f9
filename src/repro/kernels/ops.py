"""Jit'd dispatch wrappers for the Pallas kernels.

``impl="pallas"`` (the default) runs the Pallas kernel and ``impl="jnp"``
the pure-jnp reference; neither depends on the backend. Off the chip a
Pallas kernel runs only in interpret mode, which the caller asks for with
``interpret=True``.
"""

from __future__ import annotations

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.jacobi2d import jacobi2d_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.stream_triad import triad_pallas


def _use_pallas(impl: str) -> bool:
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"impl must be pallas|jnp, got {impl!r}")
    return impl == "pallas"


def triad(b, c, alpha, impl: str = "pallas", interpret: bool = False):
    if _use_pallas(impl):
        return triad_pallas(b, c, alpha, interpret=interpret)
    return ref.triad_ref(b, c, alpha)


def jacobi2d(a, impl: str = "pallas", interpret: bool = False):
    if _use_pallas(impl):
        return jacobi2d_pallas(a, interpret=interpret)
    return ref.jacobi2d_ref(a)


def matmul(a, b, impl: str = "pallas", interpret: bool = False):
    if _use_pallas(impl):
        return matmul_pallas(a, b, interpret=interpret)
    return ref.matmul_ref(a, b)


def flash_attention(q, k, v, causal: bool = True, impl: str = "pallas",
                    interpret: bool = False):
    if _use_pallas(impl):
        return flash_attention_pallas(q, k, v, causal=causal,
                                      interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal)


def mamba_scan(dt, A, B, C, x, impl: str = "pallas", interpret: bool = False):
    if _use_pallas(impl):
        return mamba_scan_pallas(dt, A, B, C, x, interpret=interpret)
    return ref.mamba_scan_ref(dt, A, B, C, x)
