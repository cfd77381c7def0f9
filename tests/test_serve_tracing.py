"""The serving path names its parts for a profiler: the compiled prefill and
decode steps carry the sublayer scopes in their ``op_name`` metadata, and
`decode_tokens` writes one host span ``serve.step`` per decode step into
the profiler's trace. Reduced configurations of both served architectures,
on the CPU."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch.serve import compile_steps, decode_tokens
from repro.models import init_params

SCOPES = ("layers", "attention", "kv_cache", "mlp", "lm_head")
ARCHS = ("granite-3-2b", "chatglm3-6b")
B, P, D = 2, 8, 3


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    cfg = get_reduced(request.param)
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    prefill, decode = compile_steps(
        cfg, params, jax.ShapeDtypeStruct((B, P), jnp.int32), None, P + D)
    return cfg, params, prefill, decode


def _op_names(compiled) -> list[str]:
    """Each instruction's ``op_name``, up to XLA's ``;`` between merged
    names."""
    return [n.split(";", 1)[0]
            for n in re.findall(r'op_name="([^"]*)"', compiled.as_text())]


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_steps_carry_every_scope(served, step):
    _, _, prefill, decode = served
    compiled = prefill if step == "prefill" else decode
    parts = {c for n in _op_names(compiled) for c in n.split("/")}
    assert [s for s in SCOPES if s not in parts] == []


def test_decode_cache_writes_sit_under_kv_cache(served):
    """Every scatter of the decode step (the new K, V and position written
    into the cache) is under ``attention/kv_cache``."""
    *_, decode = served
    scatters = re.findall(r'= \S+ scatter\(.*?op_name="([^"]*)"',
                          decode.as_text())
    assert len(scatters) == 3
    assert all("/attention/kv_cache/" in n for n in scatters), scatters


def test_decode_tokens_writes_one_serve_step_span_per_step(served,
                                                           tmp_path):
    from jax.profiler import ProfileData

    cfg, params, prefill, decode = served
    tok, _, cache = prefill(params, jnp.zeros((B, P), jnp.int32))
    jax.block_until_ready(tok)
    with jax.profiler.trace(str(tmp_path)):
        outs, _, cache = decode_tokens(cfg, decode, params, tok, cache, None,
                                       D)
        jax.block_until_ready(outs)
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert len(files) == 1
    host = ProfileData.from_file(str(files[0])).find_plane_with_name(
        "/host:CPU")
    steps = sorted((e.start_ns, dict(e.stats)) for line in host.lines
                   for e in line.events if e.name == "serve.step")
    assert [int(s["step"]) for _, s in steps] == list(range(D))
    assert np.asarray(outs).shape == (D, B, 1)
