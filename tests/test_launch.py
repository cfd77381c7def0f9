"""Launch-layer tests: sharding-rule legalization properties and an actual
jit lower+compile of train/serve steps on a 1x1 mesh (the full 512-device
dry-run runs via launch/dryrun.py; these keep the sharding code paths under
CI on one device)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.launch import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.launch.settings import SHAPES, cell_skipped, settings_for
from repro.launch.steps import make_serve_step, make_train_step
from repro.models import init_cache, init_params
from repro.optim import OptConfig, make_optimizer

SIZES = {"data": 16, "model": 16}
DP = ("data",)


def _axes_of(spec):
    for s in spec:
        if s is None:
            continue
        yield from (s if isinstance(s, (tuple, list)) else [s])


def _check_divisible(specs, shapes):
    for (kp, spec), (_, leaf) in zip(
            jax.tree_util.tree_leaves_with_path(specs),
            jax.tree_util.tree_leaves_with_path(
                shapes, is_leaf=lambda x: hasattr(x, "shape"))):
        for dim, s in zip(leaf.shape, tuple(spec)):
            if s is None:
                continue
            n = 1
            for a in (s if isinstance(s, (tuple, list)) else [s]):
                n *= SIZES[a]
            assert dim % n == 0, f"{kp}: {dim} % {n}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_legal_for_full_configs(arch):
    """Every sharded dim of every FULL-config parameter divides evenly on
    the production mesh (the dry-run requirement)."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    for fsdp in (False, True):
        specs = shd.param_specs(params, fsdp=fsdp, dp_axes=DP, dp_total=16,
                                axis_sizes=SIZES)
        _check_divisible(specs, params)
        # TP must actually engage: at least half the big weights sharded
        n_sharded = sum(1 for _, s in jax.tree_util.tree_leaves_with_path(
            specs) if any(True for _ in _axes_of(s)))
        assert n_sharded > 0


@pytest.mark.parametrize("arch", ["gemma3-1b", "jamba-1.5-large-398b",
                                  "mixtral-8x7b", "falcon-mamba-7b"])
def test_zero_specs_shard_moments(arch):
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = shd.param_specs(params, fsdp=False, dp_axes=DP, dp_total=16,
                             axis_sizes=SIZES)
    opt_init, _ = make_optimizer(OptConfig(kind="adamw"))
    opt = jax.eval_shape(opt_init, params)
    ospecs = shd.zero_specs(opt, pspecs, dp_axes=DP, dp_total=16,
                            axis_sizes=SIZES)
    _check_divisible(ospecs, opt)
    # ZeRO engaged: large moments carry a data axis
    big = [s for (kp, s), (_, l) in zip(
        jax.tree_util.tree_leaves_with_path(ospecs),
        jax.tree_util.tree_leaves_with_path(opt))
        if l.ndim >= 2 and max(l.shape) >= 1024]
    assert any("data" in list(_axes_of(s)) for s in big)


def test_cache_specs_shard_batch_and_window():
    cfg = get_config("granite-3-2b")
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 32768))
    specs = shd.cache_specs(cache, 128, DP, 16, 16)
    _check_divisible(specs, cache)
    # B=1 long-context: time dim takes the data axis
    cache1 = jax.eval_shape(lambda: init_cache(cfg, 1, 4096))
    specs1 = shd.cache_specs(cache1, 1, DP, 16, 16)
    flat = {"/".join(str(getattr(k, 'key', k)) for k in kp): s
            for kp, s in jax.tree_util.tree_leaves_with_path(specs1)}
    kspec = next(v for p, v in flat.items() if p.endswith("/k"))
    assert "data" in list(_axes_of(kspec))


@settings(max_examples=30, deadline=None)
@given(
    dims=st.lists(st.integers(min_value=1, max_value=70000), min_size=1,
                  max_size=4),
    axis=st.sampled_from(["model", "data", ("data", "model")]),
    pos=st.integers(min_value=0, max_value=3),
)
def test_property_legalize_always_divisible(dims, axis, pos):
    spec = [None] * len(dims)
    spec[min(pos, len(dims) - 1)] = axis
    out = shd.legalize(spec, tuple(dims), SIZES)
    for dim, s in zip(dims, out):
        if s is None:
            continue
        n = 1
        for a in (s if isinstance(s, (tuple, list)) else [s]):
            n *= SIZES[a]
        assert dim % n == 0


def test_train_and_serve_compile_on_host_mesh():
    """End-to-end lower+compile of the jitted steps on a 1x1 mesh."""
    cfg = dataclasses.replace(get_reduced("granite-3-2b"), n_layers=2)
    mesh = make_host_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = OptConfig()
    opt_init, _ = make_optimizer(opt_cfg)
    opt = opt_init(params)
    step = make_train_step(cfg, opt_cfg, microbatches=2)
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
             "labels": jnp.zeros((4, 16), jnp.int32)}
    with mesh:
        p2, o2, m = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(m["loss"]))

    serve = make_serve_step(cfg)
    cache = init_cache(cfg, 2, 16)
    with mesh:
        ids, logits, cache = jax.jit(serve)(
            params, jnp.zeros((2, 1), jnp.int32), cache)
    assert ids.shape == (2, 1)
    assert logits.shape == (2, cfg.padded_vocab)


def test_cell_skip_table():
    skipped = [(a, s) for a in ARCH_IDS for s in SHAPES
               if cell_skipped(a, s)]
    assert len(skipped) == 6
    assert all(s == "long_500k" for _, s in skipped)
    assert ("falcon-mamba-7b", "long_500k") not in skipped
    assert ("jamba-1.5-large-398b", "long_500k") not in skipped
    # every arch has settings
    for a in ARCH_IDS:
        assert settings_for(a).microbatches >= 1
