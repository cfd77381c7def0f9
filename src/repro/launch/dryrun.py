import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any other import (jax locks the device
# count at first backend initialisation). Everything else follows.

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.launch import sharding as shd        # noqa: E402
from repro.launch.mesh import data_axes, dp_size, make_production_mesh  # noqa: E402
from repro.launch.settings import SHAPES, cell_skipped, settings_for  # noqa: E402
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step  # noqa: E402
from repro.models import init_cache, init_params  # noqa: E402
from repro.models import moe as moe_lib          # noqa: E402
from repro.models import transformer as transformer_lib  # noqa: E402
from repro.optim import OptConfig, make_optimizer  # noqa: E402

COLLECTIVE_RE = re.compile(
    r"=\s*(\w+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[-a-z]*\(")

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

# effective data moved per device, relative to the (per-device) result shape
COLLECTIVE_FACTOR = {
    "all-gather": 1.0,       # receives (n-1)/n of the gathered result
    "all-reduce": 2.0,       # reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device collective bytes from post-SPMD optimised HLO."""
    out = {k: {"count": 0, "bytes": 0.0} for k in COLLECTIVE_FACTOR}
    total = 0.0
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        nbytes = DTYPE_BYTES.get(dt, 4)
        if dims:
            for d in dims.split(","):
                nbytes *= int(d)
        out[op]["count"] += 1
        out[op]["bytes"] += nbytes
        total += nbytes * COLLECTIVE_FACTOR[op]
    out["effective_bytes_per_device"] = total
    return out


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    cfg = get_config(arch)
    sh = SHAPES[shape_name]
    B, S = sh["global_batch"], sh["seq_len"]
    kind = sh["kind"]
    specs = {}
    if kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    elif kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    else:  # decode
        specs["token"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        specs["cache"] = _abstract(
            jax.eval_shape(lambda: init_cache(cfg, B, S)))
    if cfg.is_vlm:
        specs["ctx"] = jax.ShapeDtypeStruct(
            (B, cfg.image_tokens, cfg.d_model), jnp.bfloat16)
    elif cfg.is_encdec:
        if kind == "decode":  # decoder consumes the encoded frames
            specs["ctx"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_frames, cfg.d_model), jnp.bfloat16)
        else:
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_frames, cfg.d_model), jnp.bfloat16)
    return specs


def build_cell(arch: str, shape_name: str, mesh, *,
               n_periods_override: int | None = None,
               microbatch_override: int | None = None,
               fsdp_override: bool | None = None,
               remat_override: str | None = None):
    """Assemble (jitted_fn, abstract_args) for one (arch x shape x mesh)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    st = settings_for(arch)
    if n_periods_override is not None:
        pl = len(cfg.layer_pattern)
        cfg = _dc.replace(
            cfg, n_layers=n_periods_override * pl + cfg.n_remainder)
    if remat_override is not None:
        cfg = _dc.replace(cfg, remat=remat_override)
    if microbatch_override is not None:
        st = _dc.replace(st, microbatches=microbatch_override)
    if fsdp_override is not None:
        st = _dc.replace(st, fsdp_train=fsdp_override,
                         fsdp_serve=fsdp_override)
    sh = SHAPES[shape_name]
    B, S = sh["global_batch"], sh["seq_len"]
    kind = sh["kind"]
    dp = data_axes(mesh)
    dpn = dp_size(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    lead = dp if len(dp) > 1 else dp[0]
    batch_ok = B % dpn == 0 and B >= dpn
    # MoE dispatch: shard-local EP (default, §Perf H1) vs global scatter
    # (paper-faithful GSPMD baseline; REPRO_MOE_EP=0). EP pays one weight
    # all-gather per layer under FSDP, which only amortises at large token
    # counts — decode cells (T = B tokens) keep the global path, where
    # GSPMD contracts the sharded weight dim with a tiny activation
    # all-reduce instead (H2: observed 0.01s vs 1.99s on jamba decode).
    moe_ep = os.environ.get("REPRO_MOE_EP", "1") == "1"
    tokens_total = B * (S if kind != "decode" else 1)
    if cfg.n_experts and moe_ep and batch_ok and tokens_total >= 65536:
        moe_lib.SHARD_MAP_SPEC = (mesh, dp, "model")
        moe_lib.BUFFER_SPEC = None
    else:
        moe_lib.SHARD_MAP_SPEC = None
        moe_lib.BUFFER_SPEC = (
            shd.moe_buffer_spec(dp, dpn, sizes["model"])
            if cfg.n_experts else None)
    transformer_lib.LOGITS_SPEC = P(
        lead if batch_ok else None, None, "model")
    # Sequence-parallel residual sharding (§Perf H5): the residual stream
    # between blocks lives (batch x seq/model x d); GSPMD then decomposes
    # the per-layer output all-reduces into reduce-scatter + all-gather —
    # half the collective bytes (Korthikanti et al.; measured 27.9s->15.0s
    # on jamba-398B train). REPRO_SEQ_PARALLEL=0 restores the baseline.
    seq_par = os.environ.get("REPRO_SEQ_PARALLEL", "1") == "1"
    # H7: when EVERY layer carries an EP-dispatched MoE, the shard_map
    # boundary re-gathers the S-sharded residual each layer and the SP win
    # inverts (mixtral: 4.55s EP-only vs 7.08s EP+SP) — keep SP off there.
    from repro.models.config import MOE as _MOE
    all_moe = (cfg.n_experts > 0
               and all(f == _MOE for _, f in cfg.layer_kinds()))
    if (seq_par and batch_ok and kind in ("train", "prefill")
            and S % sizes["model"] == 0
            and not (all_moe and moe_lib.SHARD_MAP_SPEC is not None)):
        transformer_lib.ACT_SPEC = P(lead, "model", None)
    else:
        transformer_lib.ACT_SPEC = P(lead if batch_ok else None, None, None)

    params_abs = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    fsdp = st.fsdp_train if kind == "train" else st.fsdp_serve
    pspecs = shd.param_specs(params_abs, fsdp=fsdp, dp_axes=dp, dp_total=dpn,
                             axis_sizes=sizes)
    pshard = shd.named(mesh, pspecs)
    bspec = shd.batch_spec(B, dp, dpn)
    bshard = jax.NamedSharding(mesh, bspec)
    ctx_shard = jax.NamedSharding(
        mesh, shd.batch_spec(B, dp, dpn, extra_dims=2))

    specs = input_specs(arch, shape_name)

    if kind == "train":
        opt_cfg = OptConfig(kind=st.optimizer)
        opt_init, _ = make_optimizer(opt_cfg)
        opt_abs = jax.eval_shape(opt_init, params_abs)
        ospecs = shd.zero_specs(opt_abs, pspecs, dp_axes=dp, dp_total=dpn,
                                axis_sizes=sizes)
        oshard = shd.named(mesh, ospecs)
        step = make_train_step(cfg, opt_cfg, st.microbatches)
        batch = {"tokens": specs["tokens"], "labels": specs["labels"]}
        batch_shard = {"tokens": bshard, "labels": bshard}
        if "ctx" in specs or "frames" in specs:
            batch["ctx"] = specs.get("ctx", specs.get("frames"))
            batch_shard["ctx"] = ctx_shard

        def fn(params, opt_state, batch):
            return step(params, opt_state, batch)

        rep = jax.NamedSharding(mesh, P())
        jitted = jax.jit(
            fn,
            in_shardings=(pshard, oshard, batch_shard),
            out_shardings=(pshard, oshard,
                           {"loss": rep, "grad_norm": rep}),
            donate_argnums=(0, 1),
        )
        args = (params_abs, opt_abs, batch)

    elif kind == "prefill":
        step = make_prefill_step(cfg)
        cache_abs = jax.eval_shape(
            lambda: init_cache(cfg, B, S))
        cshard = shd.named(mesh,
                           shd.cache_specs(cache_abs, B, dp, dpn, sizes["model"]))
        args_list = [params_abs, specs["tokens"]]
        in_sh = [pshard, bshard]
        if "ctx" in specs or "frames" in specs:
            args_list.append(specs.get("ctx", specs.get("frames")))
            in_sh.append(ctx_shard)
        jitted = jax.jit(
            step, in_shardings=tuple(in_sh),
            out_shardings=(bshard, bshard, cshard))
        args = tuple(args_list)

    else:  # decode
        step = make_serve_step(cfg)
        cache_abs = specs["cache"]
        cspecs = shd.cache_specs(cache_abs, B, dp, dpn, sizes["model"])
        cshard = shd.named(mesh, cspecs)
        args_list = [params_abs, specs["token"], cache_abs]
        in_sh = [pshard, bshard, cshard]
        if "ctx" in specs:
            args_list.append(specs["ctx"])
            in_sh.append(ctx_shard)
        jitted = jax.jit(
            step, in_shardings=tuple(in_sh),
            out_shardings=(bshard, bshard, cshard),
            donate_argnums=(2,),
        )
        args = tuple(args_list)

    return jitted, args


def model_flops(arch: str, shape_name: str) -> float:
    """Analytic useful FLOPs: 6·N_active·D (train) / 2·N_active·D (fwd)."""
    cfg = get_config(arch)
    sh = SHAPES[shape_name]
    tokens = sh["global_batch"] * (sh["seq_len"]
                                   if sh["kind"] != "decode" else 1)
    n = cfg.active_param_count()
    return (6.0 if sh["kind"] == "train" else 2.0) * n * tokens


def _measure(arch, shape_name, mesh, **overrides) -> dict:
    """Lower+compile one variant, return raw metrics."""
    jitted, args = build_cell(arch, shape_name, mesh, **overrides)
    with mesh:
        compiled = jitted.lower(*args).compile()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": parse_collectives(hlo),
    }


def run_roofline_cell(arch: str, shape_name: str, multi_pod: bool,
                      **overrides) -> dict:
    """Exact per-device FLOPs/bytes/collectives via depth differencing.

    XLA's cost_analysis counts while-loop bodies ONCE, so any lax.scan is
    under-counted. For this tier every inner scan is disabled (CE un-chunked,
    mamba associative scan over the full sequence, attention direct) and the
    program is lowered at 1 and 2 layer-periods; metrics are then linear in
    period count and extrapolate exactly:  f(P) = f(1) + (f(2)-f(1))(P-1).
    """
    from repro.models import attention as attn_lib
    from repro.models import mamba as mamba_lib
    from repro.launch import steps as steps_lib

    mesh_name = "2x16x16" if multi_pod else "16x16"
    row = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "tier": "roofline"}
    skip = cell_skipped(arch, shape_name)
    if skip:
        row["status"] = "skipped"
        row["reason"] = skip
        return row
    t0 = time.time()
    cfg = get_config(arch)
    saved = (attn_lib.FLASH_THRESHOLD, mamba_lib.CHUNK, steps_lib.CE_CHUNK,
             transformer_lib.UNROLL_PERIODS)
    try:
        attn_lib.FLASH_THRESHOLD = 1 << 62
        mamba_lib.CHUNK = 1 << 30
        steps_lib.CE_CHUNK = 1 << 30
        transformer_lib.UNROLL_PERIODS = True
        mesh = make_production_mesh(multi_pod=multi_pod)
        ov = dict(microbatch_override=1)
        ov.update(overrides)
        f1 = _measure(arch, shape_name, mesh, n_periods_override=1, **ov)
        f2 = _measure(arch, shape_name, mesh, n_periods_override=2, **ov)
        P = cfg.n_periods

        def extra(a, b):
            return a + (b - a) * (P - 1)

        row["status"] = "ok"
        row["hlo_flops_per_device"] = extra(f1["flops"], f2["flops"])
        row["hlo_bytes_per_device"] = extra(f1["bytes"], f2["bytes"])
        coll = {}
        for op in COLLECTIVE_FACTOR:
            coll[op] = {
                "count": round(extra(f1["collectives"][op]["count"],
                                     f2["collectives"][op]["count"]), 1),
                "bytes": extra(f1["collectives"][op]["bytes"],
                               f2["collectives"][op]["bytes"]),
            }
        coll["effective_bytes_per_device"] = extra(
            f1["collectives"]["effective_bytes_per_device"],
            f2["collectives"]["effective_bytes_per_device"])
        row["collectives"] = coll
        row["model_flops_global"] = model_flops(arch, shape_name)
        row["periods"] = P
    except Exception as e:  # noqa: BLE001
        row["status"] = "error"
        row["error"] = f"{type(e).__name__}: {e}"[:2000]
        row["traceback"] = traceback.format_exc()[-4000:]
    finally:
        (attn_lib.FLASH_THRESHOLD, mamba_lib.CHUNK, steps_lib.CE_CHUNK,
         transformer_lib.UNROLL_PERIODS) = saved
    row["wall_s"] = round(time.time() - t0, 1)
    return row


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    row = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    skip = cell_skipped(arch, shape_name)
    if skip:
        row["status"] = "skipped"
        row["reason"] = skip
        return row
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        jitted, args = build_cell(arch, shape_name, mesh)
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        row["status"] = "ok"
        row["lower_s"] = round(t_lower, 1)
        row["compile_s"] = round(t_compile, 1)
        if mem is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "peak_memory_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    row[k] = int(v)
        if cost:
            row["hlo_flops_per_device"] = float(cost.get("flops", 0.0))
            row["hlo_bytes_per_device"] = float(
                cost.get("bytes accessed", 0.0))
        row["collectives"] = parse_collectives(hlo)
        row["model_flops_global"] = model_flops(arch, shape_name)
        row["hlo_chars"] = len(hlo)
    except Exception as e:  # record the failure, keep sweeping
        row["status"] = "error"
        row["error"] = f"{type(e).__name__}: {e}"[:2000]
        row["traceback"] = traceback.format_exc()[-4000:]
    row["wall_s"] = round(time.time() - t0, 1)
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape)")
    ap.add_argument("--tier", default="fit", choices=["fit", "roofline"])
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                if args.tier == "roofline":
                    row = run_roofline_cell(arch, shape, mp)
                else:
                    row = run_cell(arch, shape, mp)
                line = json.dumps(row)
                print(line, flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(line + "\n")


if __name__ == "__main__":
    main()
