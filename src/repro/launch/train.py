"""Training launcher.

On a real TPU pod this runs under the production mesh with the per-arch
sharding rules (same code path the dry-run compiles); on CPU it runs the
reduced config end-to-end. Fault tolerance (checkpoint/restart + straggler
monitoring) is always on via the supervisor.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --reduced --steps 50 --batch 8 --seq 64
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data import SyntheticLM, modality_stub
from repro.ft import TrainSupervisor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.settings import settings_for
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import OptConfig, make_optimizer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=list(ARCH_IDS))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    st = settings_for(args.arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"microbatches={st.microbatches if not args.reduced else 1}")

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = OptConfig(kind=st.optimizer, lr=args.lr,
                        warmup_steps=max(args.steps // 10, 1),
                        total_steps=args.steps)
    opt_init, _ = make_optimizer(opt_cfg)
    state = {"params": params, "opt": opt_init(params)}
    mb = 1 if args.reduced else st.microbatches
    step_jit = jax.jit(make_train_step(cfg, opt_cfg, microbatches=mb))

    data = SyntheticLM(vocab=cfg.vocab, seed=0)
    host = jax.process_index()
    ctx = None
    if cfg.is_vlm:
        ctx = jnp.asarray(modality_stub("image", args.batch,
                                        cfg.image_tokens, cfg.d_model),
                          jnp.bfloat16)
    elif cfg.is_encdec:
        ctx = jnp.asarray(modality_stub("frames", args.batch,
                                        cfg.encoder_frames, cfg.d_model),
                          jnp.bfloat16)

    def step_fn(step, st_):
        b = data.batch(step, host, args.batch, args.seq)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "labels": jnp.asarray(b["labels"])}
        if ctx is not None:
            batch["ctx"] = ctx
        with mesh:
            p, o, m = step_jit(st_["params"], st_["opt"], batch)
        if step % 10 == 0:
            print(f"  step {step:4d} loss={float(m['loss']):.4f}")
        return {"params": p, "opt": o}

    sup = TrainSupervisor(CheckpointManager(args.ckpt, keep=2,
                                            every=max(args.steps // 4, 1)))
    t0 = time.time()
    final, state = sup.run(state, step_fn, steps=args.steps)
    dt = time.time() - t0
    print(f"done: {final} steps in {dt:.1f}s "
          f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")


if __name__ == "__main__":
    main()
