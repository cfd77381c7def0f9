"""GSPMD sharding rules: parameter specs, ZeRO optimizer-state specs, input
and cache specs for every (arch x shape) cell.

Mesh axes: ("pod",) "data", "model". `pod` composes with `data` for data
parallelism / ZeRO / FSDP; `model` carries tensor parallelism (attention
heads, d_ff, vocab, mamba d_inner, per-expert d_ff).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

PyTree = Any


# ------------------------------------------------------------- param rules

def _param_spec(path: str, ndim: int, dp) -> P:
    """Base tensor-parallel spec by parameter name (path is '/'-joined)."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("wq", "wk", "wv"):
        return P(None, "model")           # (d, heads*hd)
    if leaf == "wo" and "mixer" in path:
        return P("model", None)           # (heads*hd, d)
    if leaf in ("wi_gate", "wi_up"):
        if ndim == 3:                      # MoE (E, d, f)
            return P(None, None, "model")
        return P(None, "model")           # (d, f)
    if leaf == "wo":                       # ffn down-proj
        if ndim == 3:                      # MoE (E, f, d)
            return P(None, "model", None)
        return P("model", None)           # (f, d)
    if leaf == "router":
        return P(None, None)
    if leaf == "embed":
        return P("model", None)            # (V, d) vocab-sharded
    if leaf == "lm_head":
        return P(None, "model")            # (d, V)
    if leaf == "in_proj":
        return P(None, "model")            # (d, 2*di)
    if leaf == "out_proj":
        return P("model", None)            # (di, d)
    if leaf == "conv_w":
        return P(None, "model")            # (K, di)
    if leaf in ("conv_b", "dt_bias", "D"):
        return P("model")                  # (di,), mamba2 (H,)
    if leaf == "x_proj":
        return P("model", None)            # (di, dtr+2N)
    if leaf == "dt_proj":
        return P(None, "model")            # (dtr, di)
    if leaf == "A_log":                    # mamba1 (di, N), mamba2 (H,)
        return P("model", None) if ndim == 2 else P("model")
    return P()                             # norms, gates, scalars


def _with_period_axis(spec: P, scanned: bool) -> P:
    return P(*((None,) + tuple(spec))) if scanned else spec


def _path_str(kp) -> str:
    return "/".join(
        getattr(k, "key", getattr(k, "name", str(k))) for k in kp)


def _axes_size(entry, sizes: dict[str, int]) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= sizes[a]
        return n
    return sizes[entry]


def legalize(spec: list, shape: tuple[int, ...], sizes: dict[str, int]
             ) -> list:
    """jit argument shardings require exact divisibility: relocate each
    sharded axis whose dim is not divisible to the largest dim that is,
    else replicate it (e.g. vocab=49155 moves the 'model' shard from the
    vocab dim to d_model)."""
    spec = list(spec)
    for i in range(len(spec)):
        if spec[i] is None:
            continue
        n = _axes_size(spec[i], sizes)
        if shape[i] % n == 0:
            continue
        ax = spec[i]
        spec[i] = None
        cands = [(shape[j], j) for j in range(len(spec))
                 if spec[j] is None and shape[j] % n == 0 and shape[j] >= n]
        if cands:
            _, j = max(cands)
            spec[j] = ax
    return spec


def param_specs(params: PyTree, *, fsdp: bool, dp_axes: tuple[str, ...],
                dp_total: int, axis_sizes: dict[str, int]) -> PyTree:
    """PartitionSpec tree for a parameter tree. With fsdp=True the largest
    unsharded dim of each weight additionally shards over the data axes
    (ZeRO-3 / FSDP semantics via GSPMD)."""

    def spec_for(kp, leaf):
        path = _path_str(kp)
        scanned = "periods" in path
        base = _param_spec(path, leaf.ndim - (1 if scanned else 0), dp_axes)
        spec = list(_with_period_axis(base, scanned))
        while len(spec) < leaf.ndim:
            spec.append(None)
        spec = legalize(spec, leaf.shape, axis_sizes)
        if fsdp and leaf.ndim >= 2:
            cands = [(leaf.shape[i], i) for i in range(leaf.ndim)
                     if spec[i] is None and leaf.shape[i] >= dp_total
                     and leaf.shape[i] % dp_total == 0]
            if cands:
                _, i = max(cands)
                spec[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def zero_specs(opt_state: PyTree, pspecs: PyTree, *,
               dp_axes: tuple[str, ...], dp_total: int,
               axis_sizes: dict[str, int]) -> PyTree:
    """ZeRO: optimizer moments take the param spec plus data-axis sharding
    on the largest remaining unsharded dim."""
    flat_p = {  # param path -> spec (moments mirror the param subtree)
        _path_str(kp): s
        for kp, s in jax.tree_util.tree_leaves_with_path(pspecs)
    }

    def spec_for(kp, leaf):
        path = _path_str(kp)
        if leaf.ndim == 0 or path.endswith("step"):
            return P()
        # match the param this moment mirrors: strip the leading m/v/vr/vc
        head, sub = (path.split("/", 1) + [path])[:2]
        base = flat_p.get(sub)
        if base is None or head in ("vr", "vc"):
            # factored moments have reduced rank — re-derive from scratch
            spec = [None] * leaf.ndim
        else:
            spec = list(base)[: leaf.ndim]
            while len(spec) < leaf.ndim:
                spec.append(None)
        spec = legalize(spec, leaf.shape, axis_sizes)
        flat_axes = set()
        for s in spec:
            for a in (s if isinstance(s, (tuple, list)) else [s]):
                flat_axes.add(a)
        if any(ax in flat_axes for ax in dp_axes):
            return P(*spec)
        cands = [(leaf.shape[i], i) for i in range(leaf.ndim)
                 if spec[i] is None and leaf.shape[i] >= dp_total
                 and leaf.shape[i] % dp_total == 0]
        if cands:
            _, i = max(cands)
            spec[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, opt_state)


# ---------------------------------------------------------- input specs

def batch_spec(B: int, dp_axes: tuple[str, ...], dp_total: int,
               extra_dims: int = 1) -> P:
    """Shard the batch dim over data axes when divisible, else replicate."""
    if B >= dp_total and B % dp_total == 0:
        lead = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        return P(*((lead,) + (None,) * extra_dims))
    return P(*((None,) * (extra_dims + 1)))


def cache_specs(cache_shapes: PyTree, B: int, dp_axes: tuple[str, ...],
                dp_total: int, model_total: int = 1) -> PyTree:
    """Specs for decode caches. Batch shards over the data axes and the KV
    time dimension over 'model' when divisible (a 550 GB VLM cache at
    batch=128 x 32k x 40 layers needs both); for B=1 long-context the KV
    time dimension shards over 'data' instead."""
    shard_batch = B >= dp_total and B % dp_total == 0
    lead = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def spec_for(kp, leaf):
        path = _path_str(kp)
        name = path.rsplit("/", 1)[-1]
        scanned = "periods" in path
        pre = (None,) if scanned else ()
        if name in ("k", "v"):            # (B, W, KV*hd)
            W = leaf.shape[1 + len(pre)]
            w_ax = "model" if (model_total > 1 and W % model_total == 0
                               and W >= model_total) else None
            if shard_batch:
                return P(*pre, lead, w_ax, None)
            return P(*pre, None, "data", None)
        if name == "pos":                  # (B, W)
            W = leaf.shape[1 + len(pre)]
            w_ax = "model" if (model_total > 1 and W % model_total == 0
                               and W >= model_total) else None
            if shard_batch:
                return P(*pre, lead, w_ax)
            return P(*pre, None, "data")
        if name == "h":                    # (B, di, N)
            return P(*pre, lead if shard_batch else None, "model", None)
        if name == "conv":                 # (B, K-1, di)
            return P(*pre, lead if shard_batch else None, None, "model")
        if name == "t":                    # (B,)
            return P(lead if shard_batch else None)
        return P(*((None,) * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec_for, cache_shapes)


def named(mesh, specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------- activation hints

def moe_buffer_spec(dp_axes: tuple[str, ...], dp_total: int,
                    model_total: int) -> tuple:
    """Hint tuple consumed by repro.models.moe: (capacity-dim axes,
    d-dim axis, divisors to verify against the static buffer shape)."""
    lead = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    return (lead, "model", dp_total, model_total)
