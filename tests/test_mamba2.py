"""The Mamba-2 mixer and the hybrid's decode cache on the CPU, at small
sizes: the chunked SSD prefill against the token-by-token recurrence,
prefill and decode through the cache against the benchmark's plain
reference (`chipbench/reference/granite_hybrid.py`) on the benchmark's own
weights, and the state written in place.

The comparisons with the reference run the program on float32 copies of
the benchmark's bf16 weights, so that its own gap is float32 rounding and
a lost state, which moves a Mamba-2 mixer's output by a few per cent at
these widths, stands far above it."""

import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.launch.steps import make_serve_step
from repro.models import decode_step, init_cache, init_params, prefill
from repro.models import mamba2 as mamba2_lib
from repro.models.config import MAMBA2

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

ARCH = "granite-4.0-h-micro"
P_LEN, N_DECODE = 13, 6          # a prompt over two chunks of 8, then decode


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


# ------------------------------------------------- SSD against recurrence

def test_chunked_prefill_matches_the_recurrence():
    """Prefill's chunked SSD form over a prompt of 2.6 chunks (not a
    multiple of the chunk), two groups of B and C, against the same layer
    stepped one token at a time: every output and the final state and
    conv tail agree to float32 rounding."""
    cfg = dataclasses.replace(get_reduced(ARCH), ssm_groups=2)
    p = _f32(mamba2_lib.mamba2_init(jax.random.PRNGKey(3), cfg))
    # slow decays (A in [-0.1, -0.01]), so the state carries across chunks
    p["A_log"] = jnp.log(jnp.linspace(0.01, 0.1, cfg.ssm_heads))
    p["dt_bias"] = jnp.zeros_like(p["dt_bias"])
    B, S = 2, 21
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.d_model))
    out, cache = mamba2_lib.mamba2_forward(p, cfg, x, return_state=True)

    def step(c, xt):
        o, c = mamba2_lib.mamba2_decode_step(p, cfg, xt[:, None], c)
        return c, o[:, 0]

    c0 = jax.tree.map(lambda a: a.astype(jnp.float32),
                      mamba2_lib.mamba2_init_cache(cfg, B))
    c_seq, outs = jax.lax.scan(step, c0, jnp.moveaxis(x, 1, 0))
    np.testing.assert_allclose(out, jnp.moveaxis(outs, 0, 1),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(cache["h"], c_seq["h"], rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(cache["conv"], c_seq["conv"], rtol=0, atol=0)
    assert float(jnp.abs(cache["h"]).max()) > 0


# ---------------------------------------------- against the reference

def _conf(cfg) -> dict:
    """The configuration file, cut to the reduced program's sizes."""
    conf = json.loads((ROOT / "chipbench/configs/granite-4.0-h-micro.json")
                      .read_text())
    return dict(
        conf, hidden_size=cfg.d_model, shared_intermediate_size=cfg.d_ff,
        num_hidden_layers=cfg.n_layers, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads, vocab_size=cfg.vocab,
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_expand=cfg.ssm_inner // cfg.d_model,
        mamba_d_state=cfg.ssm_state, mamba_chunk_size=cfg.ssm_chunk,
        attention_multiplier=cfg.resolved_head_dim ** -0.5,
        layer_types=conf["layer_types"][:cfg.n_layers])


@pytest.fixture(scope="module")
def hybrid():
    """The reduced program, the benchmark's weights for it (float32
    copies, and as drawn), tokens, and the reference's logits over them."""
    from chipbench import spec, weights

    cfg = get_reduced(ARCH)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    drawn = weights.maker(shapes)(weights.seed_words(3416000007))
    params = _f32(drawn)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, P_LEN + N_DECODE),
                                0, cfg.vocab)
    ref = spec.reference("granite_hybrid")
    dims = ref.dims(_conf(cfg))
    want = ref.logits(params, dims, tokens, P_LEN - 1)
    return cfg, params, tokens, np.asarray(want), drawn


def _zero_state(cache):
    per = {n: (dict(c, h=jnp.zeros_like(c["h"])) if "h" in c else c)
           for n, c in cache["periods"].items()}
    return dict(cache, periods=per)


def _served_logits(cfg, params, tokens, fault=None):
    """Prefill's last logits, then each decode step's, (n, N_DECODE + 1,
    V): prompt through the cache, then the rest of ``tokens`` one at a
    time."""
    last, cache = prefill(params, cfg, tokens[:, :P_LEN],
                          cache_len=P_LEN + N_DECODE)
    out = [last[:, -1]]
    step = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c))
    for i in range(P_LEN, P_LEN + N_DECODE):
        if fault == "zeroed_state":
            cache = _zero_state(cache)
        logits, cache = step(params, tokens[:, i:i + 1], cache)
        out.append(logits[:, 0])
    return np.asarray(jnp.stack(out, axis=1))[..., :cfg.vocab]


def _gap(hybrid, fault=None, monkeypatch=None) -> float:
    cfg, params, tokens, want, _ = hybrid
    if fault == "no_inter_chunk_carry":
        chunk = mamba2_lib._chunk
        monkeypatch.setattr(mamba2_lib, "_chunk", lambda h, inp, A: chunk(
            jnp.zeros_like(h), inp, A))
    got = _served_logits(cfg, params, tokens, fault)
    return float(np.max(np.abs(got - want)))


@pytest.fixture(scope="module")
def sound_gap(hybrid) -> float:
    return _gap(hybrid)


def test_prefill_decode_matches_the_reference(hybrid, sound_gap):
    """Prefill over two chunks, then decode through the cache, gives the
    reference's logits at every position to float32 rounding."""
    want = hybrid[3]
    assert sound_gap <= 1e-3 * float(np.max(np.abs(want)))


@pytest.mark.parametrize("fault", ["zeroed_state", "no_inter_chunk_carry"])
def test_a_lost_state_moves_the_gap_tenfold(hybrid, sound_gap, fault,
                                            monkeypatch):
    """Zeroing the carried state before each decode step, or starting each
    prefill chunk from no state, moves the widest logit gap at least ten
    times past the sound program's."""
    assert _gap(hybrid, fault, monkeypatch) >= 10 * sound_gap


def test_bf16_program_tracks_the_reference(hybrid):
    """As served, in bf16, the program stays within bf16 rounding of the
    float32 reference (relative L2 over the logits)."""
    cfg, _, tokens, want, drawn = hybrid
    got = _served_logits(cfg, drawn, tokens)
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 2 * 2 ** -8 * np.sqrt(cfg.n_layers), rel


# ------------------------------------------------ the state in place

B, W = 2, 16


def _hybrid_decode():
    cfg = get_reduced(ARCH)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, W))
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    lowered = jax.jit(make_serve_step(cfg), donate_argnums=(2,)).lower(
        params, token, cache)
    return cfg, params, cache, lowered


def test_decode_writes_one_state_slice_per_layer():
    """Each Mamba-2 layer's state is written as one slice of the stacked
    buffer, (B, H, P, N); no write in the decode program is as large as
    the stack."""
    from test_decode_inplace import _writes

    cfg, _, cache, lowered = _hybrid_decode()
    updates = [n for _, n in _writes(lowered.as_text(dialect="hlo"))]
    h = cache["periods"]["l0"]["h"]
    layer = int(np.prod(h.shape[1:]))
    n_mamba = cfg.layer_pattern.count(MAMBA2)
    assert cfg.n_periods > 1 and updates.count(layer) == n_mamba, updates
    assert max(updates) < int(np.prod(h.shape)), updates


def test_decode_state_aliases_its_donated_input():
    """Every cache leaf of the compiled decode step, the state stacks
    among them, is an output that reuses its donated input buffer (the
    step's scratch at the cell's widths is `test_tpu_compile.py`'s)."""
    _, params, cache, lowered = _hybrid_decode()
    header = lowered.compile().as_text().split("\n", 1)[0]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{\}", header)}
    first = len(jax.tree.leaves(params)) + 1          # after the token
    leaves = range(first, first + len(jax.tree.leaves(cache)))
    assert aliased >= set(leaves), header
