"""The decode step writes its KV cache in place: each attention layer
writes one row per batch element into the stacked buffer, and nothing in
the step copies a layer's buffer or the stack. Read from the lowered
program (before XLA optimises it) and from the compiled one on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.launch.steps import make_serve_step
from repro.models import init_cache, init_params

B, W = 2, 16

_DEF = re.compile(r"^\s*(?:ROOT )?([\w.\-]+) = \w+\[([\d,]*)\]", re.M)
_WRITE = re.compile(
    r"^\s*(?:ROOT )?[\w.\-]+ = \S+ (dynamic-update-slice|scatter)\(([^)]*)\)",
    re.M)


def _writes(hlo_text: str) -> list[tuple[str, int]]:
    """(op, elements of its update) for every dynamic-update-slice and
    scatter in an HLO module's text."""
    size = {m.group(1): int(np.prod([int(d) for d in m.group(2).split(",")
                                     if d]))
            for m in _DEF.finditer(hlo_text)}
    out = []
    for m in _WRITE.finditer(hlo_text):
        args = [a.strip() for a in m.group(2).split(",")]
        if m.group(1) == "dynamic-update-slice":
            updates = [args[1]]
        else:                       # operands..., indices, updates...
            n = (len(args) - 1) // 2
            updates = args[n + 1:]
        out += [(m.group(1), size[u]) for u in updates]
    return out


def _granite_decode():
    cfg = get_reduced("granite-3-2b")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, W))
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    lowered = jax.jit(make_serve_step(cfg), donate_argnums=(2,)).lower(
        params, token, cache)
    return cfg, params, cache, lowered


def test_decode_writes_rows_not_layer_buffers():
    """No write in the decode program is as large as one layer's K (or V)
    buffer; the K and V writes are one row of KV*hd per batch element."""
    cfg, _, _, lowered = _granite_decode()
    assert cfg.n_periods > 1                 # a stacked cache
    row = cfg.n_kv_heads * cfg.resolved_head_dim
    layer = B * W * row
    writes = _writes(lowered.as_text(dialect="hlo"))
    assert [n for op, n in writes if op == "scatter"].count(B * row) == 2
    assert all(n < layer for _, n in writes), writes


def test_decode_cache_aliases_its_donated_input():
    """Every cache leaf of the compiled decode step is an output that
    reuses its donated input buffer."""
    _, params, cache, lowered = _granite_decode()
    header = lowered.compile().as_text().split("\n", 1)[0]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{\}", header)}
    first = len(jax.tree.leaves(params)) + 1          # after the token
    leaves = range(first, first + len(jax.tree.leaves(cache)))
    assert aliased >= set(leaves), header
