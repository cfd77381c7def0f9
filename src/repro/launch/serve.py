"""Serving launcher: prefill a batch of prompts, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --batch 4 --prompt-len 16 --decode 16

Both steps are compiled ahead of time and reported as set-up; prefill and
decode times wait for the device (``block_until_ready``). Off the chip run
it with ``JAX_PLATFORMS=cpu``; `chip_smoke.py` at the repo root drives the
same `serve` function at granite-3-2b's full widths on one TPU.

With ``--svm-budget-frac`` the decode loop additionally rides the SVM
weight-streaming runtime: the model's parameter leaves are planned into
managed ranges against a device pool of the given fraction of total param
bytes, and the whole decode's layer-fetch trace replays through the
compiled-session engine in one fused pass (`StreamingExecutor.
decode_steps` — the per-token segment records and compiles once, then all
N tokens execute as a single concatenated mega-trace; prefetch mode falls
back to per-token `decode_step` replays), reporting the simulated
streaming wall clock, migration/eviction traffic, and session cache stats
next to the real tok/s.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --svm-budget-frac 0.6 --svm-mode svm_aware

With ``--requests N`` (N > 1) the report switches to the **multi-tenant
scheduler** (`repro.svm.scheduler`): N decode requests of this model, a
seeded synthetic arrival process (``--arrival`` = mean interarrival
seconds on the simulated clock; 0 = all at once), contending for one
shared SVM pool under ``--sched-policy fifo|admission|svm_aware`` —
per-request latency percentiles, aggregate tok/s, and eviction pressure
ride along the real decode's tok/s.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
        --svm-budget-frac 0.6 --requests 8 --sched-policy svm_aware
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data import SyntheticLM, modality_stub
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import init_params


class WeightStream:
    """SVM weight-streaming accounting riding along a real decode loop.

    Each parameter leaf is one fetch group, visited in model order once
    per token; per-leaf decode FLOPs are estimated as 2 · batch · params.
    All manager driving goes through the executor's `TraceSession` — the
    per-token trace compiles once and replays as cached segments."""

    def __init__(self, params, batch: int, *, budget_frac: float,
                 policy: str, mode: str):
        from repro.svm import StreamingExecutor

        paths, nbytes, nparams = [], [], []
        for path, leaf in StreamingExecutor._leaves(params):
            paths.append(path)
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            nparams.append(n)
            nbytes.append(n * leaf.dtype.itemsize)
        total = sum(nbytes)
        budget = max(int(total * budget_frac), 1)

        kw: dict = {}
        if mode == "svm_aware":
            # pin the embedding-ish hottest leaf (only if it leaves room
            # for streaming the rest — a pinned-full pool deadlocks every
            # later migration) and prefetch the rest
            hot = int(np.argmax(nbytes))
            kw = {"prefetch": True}
            if nbytes[hot] <= budget // 2:
                kw["pin"] = (paths[hot],)
        elif mode == "measured":
            # docs/prefetching.md: profile the first token's touch
            # columns and pin only leaves above the touch-frequency
            # threshold — the measured alternative to svm_aware's
            # hand-picked pin + aggressive prefetch
            kw = {"prefetch_mode": "measured"}
        elif mode == "zero_copy":
            # paper §4.2 hybrid placement: coldest (largest) leaves stay
            # host-resident at remote-access cost, up to half the weights
            order = sorted(range(len(paths)), key=lambda i: -nbytes[i])
            zc, acc = [], 0
            for i in order:
                if acc + nbytes[i] > total // 2:
                    continue     # too big for the budget; smaller may fit
                zc.append(paths[i])
                acc += nbytes[i]
            kw = {"zero_copy": tuple(zc)}

        self.executor = StreamingExecutor(
            params, budget, policy=policy, profile=False, **kw)
        self.layer_paths = [[p] for p in paths]
        self.flops = [2.0 * batch * n for n in nparams]
        self.total_bytes = total
        self.budget = budget

    def step(self) -> None:
        self.executor.decode_step(self.layer_paths, self.flops,
                                  materialize=False)

    def steps(self, n: int) -> None:
        """Fused multi-token accounting: all ``n`` decode steps replay as
        one concatenated segment in a single batched engine pass
        (`decode_steps`; prefetch mode falls back to the per-token
        loop)."""
        self.executor.decode_steps(self.layer_paths, self.flops, n,
                                   materialize=False)

    def report(self, decoded: int) -> str:
        m = self.executor.metrics()
        return (
            f"svm stream (simulated): DOS {m['dos']:.0f}% "
            f"(pool {self.budget / 1e6:.1f}MB / "
            f"weights {self.total_bytes / 1e6:.1f}MB), "
            f"simulated decode wall {m['wall_s'] * 1e3:.2f}ms, "
            f"{m['migrations']} migs / {m['evictions']} evicts "
            f"(e2m {m['evict_to_mig']:.2f}), "
            f"session: {m['segment_cache_misses']} compiled / "
            f"{m['segment_cache_hits']} cached replays over "
            f"{decoded} tokens")


def decode_tokens(cfg, serve_step, params, tok, cache, ctx, steps: int):
    """Greedy-decode ``steps`` tokens through a (jitted) serve step.

    Encoder-decoder configs re-encode their modality context and thread
    it through every step; VLMs thread the precomputed image context.
    Decoder-only configs (``ctx`` is None) take the three-argument path.
    Returns (decoded token list, per-step logits list, final cache).

    Each turn of the loop is a host span ``serve.step`` (argument ``step``,
    from 0) in the profiler's trace, on the device trace's clock; with no
    profiler running it costs a check."""
    outs, logits = [], []
    for i in range(steps):
        with jax.profiler.TraceAnnotation("serve.step", step=i):
            if ctx is not None and (cfg.is_encdec or cfg.is_vlm):
                from repro.models import encode
                c = encode(params, cfg, ctx) if cfg.is_encdec else ctx
                tok, lg, cache = serve_step(params, tok, cache, c)
            else:
                tok, lg, cache = serve_step(params, tok, cache)
            outs.append(tok)
            logits.append(lg)
    return outs, logits, cache


def _chaos_line(r: dict) -> str:
    """One-line chaos/recovery summary (empty without an injector)."""
    ch = r.get("chaos")
    if not ch or "injector" not in ch:
        return ""
    return (
        f"\n  chaos[{ch['injector']['plan']} seed "
        f"{ch['injector']['seed']}]: "
        f"{ch['injector']['events_applied']}/"
        f"{ch['injector']['events_total']} events, "
        f"{ch['migration_faults']} migration faults / "
        f"{ch['retries']} retries ({ch['retry_exhausted']} exhausted), "
        f"{ch['crashes']} crashes, {ch['preemptions']} preemptions, "
        f"{ch['resumes']} resumes, {ch['degraded_rounds']} degraded "
        f"rounds, {r['n_failed']} failed, "
        f"backoff {ch['backoff_wall_s'] * 1e3:.2f}ms")


def schedule_report(r: dict) -> str:
    """Three-line human summary of a `run_schedule` result dict (plus a
    chaos/recovery line when a fault plan was injected)."""
    sc = r["shared_cache"]
    return (
        f"svm sched[{r['policy']}] (simulated): {r['n_requests']} reqs, "
        f"offered DOS {r['dos_offered']:.0f}% "
        f"(peak admitted {r['dos_peak']:.0f}%), "
        f"p50/p90/p99 latency "
        f"{r['latency_p50_s'] * 1e3:.1f}/{r['latency_p90_s'] * 1e3:.1f}/"
        f"{r['latency_p99_s'] * 1e3:.1f}ms, "
        f"agg {r['agg_tok_s']:.0f} tok/s\n"
        f"  {r['migrations']} migs / {r['evictions']} evicts "
        f"(e2m {r['evict_to_mig']:.2f}, "
        f"{r['evictions_per_token']:.2f} ev/tok), "
        f"segment hit rate {r['segment_hit_rate'] * 100:.1f}% "
        f"({r['segment_shared_hits']} cross-request replays)\n"
        f"  shared cache: {sc['shared_segments']} segments, "
        f"{sc['shared_lookup_hits']} hits / "
        f"{sc['shared_lookup_misses']} misses, "
        f"{sc['shared_relocations']} relocations, "
        f"{sc['shared_concats']} round concats "
        f"({'fused' if r.get('fused') else 'per-token'} replay)"
        + _chaos_line(r))


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=list(ARCH_IDS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode", type=int, default=16)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--svm-budget-frac", type=float, default=0.0,
                    help="enable SVM weight-streaming accounting with a "
                         "device pool of this fraction of the param bytes")
    ap.add_argument("--svm-policy", default="lrf",
                    choices=["lrf", "lru", "clock", "random"])
    ap.add_argument("--svm-mode", default="naive",
                    choices=["naive", "svm_aware", "measured",
                             "zero_copy"])
    ap.add_argument("--requests", type=int, default=1,
                    help="multi-tenant: N concurrent decode requests of "
                         "this model over one shared SVM pool (needs "
                         "--svm-budget-frac)")
    ap.add_argument("--arrival", type=float, default=0.0,
                    help="mean interarrival seconds (simulated Poisson "
                         "process; 0 = all requests arrive at once)")
    ap.add_argument("--sched-policy", default="svm_aware",
                    choices=["fifo", "admission", "svm_aware"])
    ap.add_argument("--admit-by", default="bytes",
                    choices=["bytes", "measured"],
                    help="what the admission watermark caps: total plan "
                         "bytes, or the measured resident working set "
                         "estimated from the spec's own touch columns "
                         "(docs/prefetching.md)")
    ap.add_argument("--chaos", action="store_true",
                    help="inject the default seeded fault plan into the "
                         "multi-tenant schedule (capacity loss, slow "
                         "pages, migration faults, a crash) and report "
                         "the recovery accounting")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the default fault plan")
    ap.add_argument("--chaos-intensity", type=float, default=1.0,
                    help="scales the number of injected migration faults")
    ap.add_argument("--thrash-watermark", type=float, default=None,
                    help="evictions-per-token watermark for the runtime "
                         "thrash guard (preempt + tighten admission); "
                         "unset = guard off")
    args = ap.parse_args(argv)
    if args.requests > 1 and args.svm_budget_frac <= 0.0:
        ap.error("--requests > 1 needs --svm-budget-frac > 0 "
                 "(the shared pool is sized from it)")
    return args


def compile_steps(cfg, params, prompts, ctx, cache_len: int):
    """Compile the prefill step and the decode step ahead of time.

    Arguments may be arrays or `jax.ShapeDtypeStruct`s (with shardings on
    described devices, for compiling without the chip). The decode step
    donates its cache argument, so one step holds one copy of the cache.
    Returns (prefill, decode) compiled executables; the decode step takes
    the context that `decode_tokens` threads (encoded, for enc-dec)."""
    from repro.models import encode

    pre_args = (params, prompts) + ((ctx,) if ctx is not None else ())
    lowered = jax.jit(make_prefill_step(cfg, cache_len)).lower(*pre_args)
    prefill_c = lowered.compile()
    tok, _, cache = jax.tree.map(
        lambda info, sh: jax.ShapeDtypeStruct(info.shape, info.dtype,
                                              sharding=sh),
        lowered.out_info, prefill_c.output_shardings)
    dec_args = (params, tok, cache)
    if ctx is not None:
        dec_args += ((jax.eval_shape(lambda p, c: encode(p, cfg, c),
                                     params, ctx)
                      if cfg.is_encdec else ctx),)
    serve_c = jax.jit(make_serve_step(cfg),
                      donate_argnums=(2,)).lower(*dec_args).compile()
    return prefill_c, serve_c


def serve(args: argparse.Namespace) -> dict:
    """Run the serving path once: prefill a batch of prompts, greedy-decode
    ``args.decode`` tokens through the cache, and ride the SVM accounting
    (and, with ``args.requests > 1``, the multi-tenant schedule) along.

    Both steps are compiled before any clock starts (``compile_s``), and
    every clock read waits for the device. ``logits`` holds the prefill's
    last-position logits and each decode step's, (B, decode + 1, V), and
    ``tokens`` the greedy continuation they picked."""
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))

    stream = None
    if args.svm_budget_frac > 0.0:
        stream = WeightStream(params, args.batch,
                              budget_frac=args.svm_budget_frac,
                              policy=args.svm_policy, mode=args.svm_mode)

    data = SyntheticLM(vocab=cfg.vocab, seed=1)
    prompts = jnp.asarray(
        data.batch(0, 0, args.batch, args.prompt_len)["tokens"])
    ctx = None
    if cfg.is_vlm:
        ctx = jnp.asarray(modality_stub("image", args.batch,
                                        cfg.image_tokens, cfg.d_model),
                          jnp.bfloat16)
    elif cfg.is_encdec:
        ctx = jnp.asarray(modality_stub("frames", args.batch,
                                        cfg.encoder_frames, cfg.d_model),
                          jnp.bfloat16)
    pre_args = (params, prompts) + ((ctx,) if ctx is not None else ())

    with mesh:
        jax.block_until_ready(pre_args)
        t0 = time.perf_counter()
        prefill_c, serve_c = compile_steps(
            cfg, params, prompts, ctx, args.prompt_len + args.decode)
        t_compile = time.perf_counter() - t0

        t0 = time.perf_counter()
        tok, logits, cache = prefill_c(*pre_args)
        jax.block_until_ready((tok, logits, cache))
        t_pre = time.perf_counter() - t0

        t0 = time.perf_counter()
        decoded, step_logits, cache = decode_tokens(
            cfg, serve_c, params, tok, cache, ctx, args.decode)
        jax.block_until_ready((decoded, step_logits, cache))
        t_dec = time.perf_counter() - t0
        # the streaming accounting is a pure function of the token count:
        # replay it outside the timed loop so tok/s stays the real number
        if stream is not None:
            stream.steps(args.decode)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    res = {
        "cfg": cfg,
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "decode": args.decode,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "compile_s": t_compile,
        "prefill_s": t_pre,
        "decode_s": t_dec,
        "decode_per_token_s": t_dec / max(args.decode, 1),
        "tok_s": args.batch * args.decode / max(t_dec, 1e-9),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "params": params,
        "prompts": prompts,
        "tokens": jnp.concatenate([tok] + decoded, axis=1),
        "logits": jnp.stack([logits] + step_logits, axis=1),
        "stream": stream.report(args.decode) if stream is not None else None,
        "schedule": None,
    }
    if args.requests > 1:
        # multi-tenant accounting: N requests of this model contending
        # for one shared pool (pure simulation — rides the same clock
        # as the single-stream report above)
        from repro.svm import FaultPlan, ModelSpec, run_schedule
        spec = ModelSpec.from_params(args.arch, params, batch=args.batch)
        pool = max(int(spec.total_bytes * args.svm_budget_frac), 1)
        plan = None
        if args.chaos:
            plan = FaultPlan.default(args.chaos_seed,
                                     n_requests=args.requests,
                                     tokens=args.decode,
                                     intensity=args.chaos_intensity)
        res["schedule"] = run_schedule(
            [spec], args.requests, pool, policy=args.sched_policy,
            admit_by=args.admit_by,
            seed=0, mean_interarrival_s=args.arrival,
            tokens=args.decode, evict_policy=args.svm_policy,
            fault_plan=plan, thrash_watermark=args.thrash_watermark)
    return res


def report_lines(res: dict) -> list[str]:
    """Human summary of a `serve` result: device, widths, set-up and
    device-timed step times, peak device bytes, simulated SVM reports."""
    c = res["cfg"]
    dev = res["device"]
    peak = res["peak_bytes_in_use"]
    lines = [
        f"device: {dev['platform']} {dev['kind']} x{dev['count']}",
        f"model: {c.name} {c.n_layers} layers, d_model {c.d_model}, "
        f"{c.n_heads} heads ({c.n_kv_heads} kv), d_ff {c.d_ff}, "
        f"vocab {c.vocab}",
        f"compile (set-up): {res['compile_s']:.3f}s",
        f"prefill {res['batch']}x{res['prompt_len']}: "
        f"{res['prefill_s'] * 1e3:.3f}ms; decode {res['decode']} tokens: "
        f"{res['decode_per_token_s'] * 1e3:.3f}ms/token "
        f"({res['tok_s']:.1f} tok/s)",
        "peak device bytes: "
        + (f"{peak}" if peak is not None else "not reported by backend"),
    ]
    if res["stream"] is not None:
        lines.append(res["stream"])
    if res["schedule"] is not None:
        lines.append(schedule_report(res["schedule"]))
    return lines


def main(argv: list[str] | None = None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    res = serve(args)
    print("\n".join(report_lines(res)))
    print("first request continuation:", res["tokens"][0].tolist())


if __name__ == "__main__":
    main()
