"""Chip smoke test: the serving path once on one TPU, at granite-3-2b's
published widths (40 layers, d_model 2048, vocab 49155; random weights from
a fixed seed).

    python chip_smoke.py

It calls `repro.launch.serve.serve`, the function behind the serving CLI,
with batch 8, a 512-token prompt and 32 decoded tokens, plus the simulated
SVM weight stream and an 8-request schedule. Then, on the chip, it checks
the logits that prefill and each decode step produced through the KV cache
against `forward` run over the same tokens in one pass.

It prints the device, the widths, compile set-up time, device-timed
prefill and per-token decode, peak device bytes and the simulated SVM
lines; its last line is one JSON object. Without a TPU it exits non-zero
and prints no result. It runs in one process and starts no other.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SERVE_ARGS = ["--arch", "granite-3-2b", "--batch", "8", "--prompt-len", "512",
              "--decode", "32", "--svm-budget-frac", "0.6",
              "--svm-mode", "svm_aware", "--requests", "8"]

# Both paths run in bf16 with f32 softmax and normalisation. Each layer
# rounds its bf16 outputs by up to 2**-8 of their size, and the cached
# decode and the one-pass forward are different programs that round in
# different places, so their difference grows like a random walk over the
# depth: about 2**-8 * sqrt(n_layers) of the logits, as a vector over the
# vocabulary in the L2 norm. The bound is twice that: 4.9 % at 40 layers,
# 1.6 % at the reduced config's 4. The first compared position comes from
# prefill alone, with no cache, and shows the two programs' own drift. A
# cache that loses prompt positions drifts further with every decoded
# token: the old prompt-wide cache reached 19 % at 40 layers on one v5e and
# 4 % at 4 layers on the CPU, and the CPU regression test holds it to
# failing this bound.
def logits_rtol(n_layers: int) -> float:
    return 2.0 * 2.0**-8 * n_layers**0.5


def check_logits(res: dict) -> dict:
    """Compare the served logits with `forward` over the same tokens.

    The prompt plus every token fed back into decode is run through
    `forward` once; its logits at positions P-1 .. P+D-1 are what prefill
    and the D decode steps should have produced. Per position the error is
    ``|served - forward| / |forward|`` (L2 over the vocabulary, worst
    sequence of the batch). Returns the worst position's error, the first
    (prefill-only) position's, and whether the worst is within
    `logits_rtol`."""
    import jax
    import jax.numpy as jnp

    from repro.models import forward

    cfg, P = res["cfg"], res["prompt_len"]
    seq = jnp.concatenate([res["prompts"], res["tokens"][:, :-1]], axis=1)

    @jax.jit
    def rel_err(params, seq, served):
        ref = forward(params, cfg, seq)[0][:, P - 1:, :cfg.vocab]
        ref = ref.astype(jnp.float32)
        got = served[..., :cfg.vocab].astype(jnp.float32)
        err = (jnp.linalg.norm(got - ref, axis=-1)
               / jnp.linalg.norm(ref, axis=-1))
        return jnp.max(err, axis=0)

    err = jax.device_get(rel_err(res["params"], seq, res["logits"]))
    rtol = logits_rtol(cfg.n_layers)
    return {"max_rel_err": float(err.max()), "prefill_rel_err": float(err[0]),
            "rtol": rtol, "positions": len(err),
            "ok": bool(err.max() <= rtol)}


def smoke(argv: list[str]) -> tuple[dict, dict]:
    """Serve once with the CLI arguments ``argv`` and check the logits."""
    from repro.launch.serve import parse_args, serve
    res = serve(parse_args(argv))
    return res, check_logits(res)


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {backend!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import report_lines

    print(f"compile cache: {enable_compile_cache()}")
    res, chk = smoke(SERVE_ARGS)
    print("\n".join(report_lines(res)))
    print(f"logits check vs forward (on chip, {chk['positions']} positions "
          f"x {res['batch']} sequences): worst relative L2 error "
          f"{chk['max_rel_err']:.6f} (prefill-only position "
          f"{chk['prefill_rel_err']:.6f}), tolerance {chk['rtol']:.6f}: "
          + ("pass" if chk["ok"] else "FAIL"))
    if not chk["ok"]:
        return 1
    print(json.dumps({"ok": True, "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
