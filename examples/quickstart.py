"""Quickstart: build a small LM from the public API, train a few steps on
synthetic data, checkpoint, and decode — all on CPU.

    PYTHONPATH=src python examples/quickstart.py
"""

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.data import SyntheticLM
from repro.launch.steps import make_serve_step, make_train_step
from repro.models import init_cache, init_params, prefill
from repro.optim import OptConfig, make_optimizer


def main() -> None:
    cfg = dataclasses.replace(get_reduced("granite-3-2b"), n_layers=4)
    print(f"model: {cfg.name} reduced ({cfg.param_count()/1e6:.2f}M params)")

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=5, total_steps=60)
    opt_init, _ = make_optimizer(opt_cfg)
    opt_state = opt_init(params)
    train_step = jax.jit(make_train_step(cfg, opt_cfg))

    data = SyntheticLM(vocab=cfg.vocab, seed=0)
    t0 = time.time()
    for step in range(30):
        batch = {k: jnp.asarray(v)
                 for k, v in data.batch(step, 0, 8, 64).items()}
        params, opt_state, metrics = train_step(params, opt_state, batch)
        if step % 10 == 0 or step == 29:
            print(f"step {step:3d}  loss={float(metrics['loss']):.4f}  "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
    print(f"trained 30 steps in {time.time()-t0:.1f}s")

    # greedy decode a few tokens from a prompt
    prompt = jnp.asarray(data.batch(999, 0, 1, 8)["tokens"])
    logits, cache = prefill(params, cfg, prompt, cache_len=32)
    serve = jax.jit(make_serve_step(cfg))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [int(tok[0, 0])]
    for _ in range(8):
        tok, _, cache = serve(params, tok, cache)
        out.append(int(tok[0, 0]))
    print("decoded continuation ids:", out)


if __name__ == "__main__":
    main()
