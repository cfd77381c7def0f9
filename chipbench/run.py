"""Benchmark entry: one run of one cell on the chip this process finds.

    python3 chipbench/run.py --workload granite-3-2b.decode --seed 7 \
        --seconds 30 --trace 0

``--trace 0`` sets up, serves closed-loop rounds for ``--seconds`` (whole
rounds: the window ends with the round in flight when the time is up) and
reports the cell's end-to-end metrics. ``--trace 1`` sets up, serves one
round under the profiler and reports the per-layer metrics read from its
trace. Both then check the served tokens against the plain reference
(`check.py`). The last line of standard output is one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result. JAX's persistent compilation cache lives in
``.chipbench_cache/jax`` inside the checkout, so only a checkout's first
run of a cell compiles."""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from here, before any import

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".chipbench_cache"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class NoChip(RuntimeError):
    pass


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def configure_jax():
    """Compilation cache inside the checkout, at a fixed path; every
    program cached, however fast it compiled."""
    (CACHE / "tpu_logs").mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def find_chips(jax, n: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def check_program_config(cfg, dims: dict) -> None:
    """The program's configuration must be the one the file states."""
    got = {
        "d_model": cfg.d_model, "n_layers": cfg.n_layers,
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
        "vocab": cfg.vocab, "tie_embeddings": cfg.tie_embeddings,
        "rotary_dim": int(cfg.resolved_head_dim * cfg.partial_rotary),
        "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
        "attn_scale": cfg.resolved_head_dim ** -0.5,
        "embed_scale": 1.0, "residual_scale": 1.0, "logit_divisor": 1.0,
    }
    if cfg.embed_scale:
        got["embed_scale"] = cfg.d_model ** 0.5
    bad = {k: (v, dims[k]) for k, v in got.items() if v != dims[k]}
    if bad:
        raise ValueError(f"program config differs from the file "
                         f"(program, file): {bad}")


class CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self, jax):
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1


def measure(cell, seed: int, seconds: float, traced: bool, device,
            t_start: float, program_cfg=None, step_wrap=None) -> dict:
    """Everything after the look for a chip: set-up, the window (or the
    traced round), the reference check and the metrics. Returns the result
    object. ``program_cfg`` and ``step_wrap`` let tests run the program
    small and break its decode step underneath."""
    import jax

    from chipbench import check, harness, spec, trace, work
    from chipbench import traffic as traffic_lib
    from repro.configs import get_config

    ref = spec.reference(cell.config["reference"])
    dims = ref.dims(cell.config)
    cfg = program_cfg or get_config(cell.config["arch"])
    check_program_config(cfg, dims)
    tr = traffic_lib.validate(cell.traffic)
    counter = CompileCounter(jax)

    server = harness.set_up(cfg, tr, seed, dims["vocab"], device,
                            step_wrap)
    setup_s = time.perf_counter() - t_start

    summary = None
    counter.active = True
    if traced:
        tdir = CACHE / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        time.sleep(1.0)   # let the device tracer start before the round
        try:
            r = server.serve_round(server.prompts(0), time.perf_counter())
        finally:
            jax.profiler.stop_trace()
        window = harness.Window([r], r.start, float(r.deliveries[-1]))
    else:
        window = harness.run_window(server, seconds)
    counter.active = False
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    if traced:
        summary = trace.summarize(trace.load(tdir, harness.SPANS),
                                  prefill="prefill_step", decode="serve_step")
        shutil.rmtree(tdir, ignore_errors=True)

    # the program's state is freed (each round drops its cache and
    # logits); the weights are the benchmark's own
    t_ref = time.perf_counter()
    B = tr["batch"]
    picks = check.sample(len(window.rounds), B,
                         cell.check["sample_requests"], seed)
    seqs, served = check.sequences(
        tr, dims["vocab"], seed, picks,
        lambda r, row: window.rounds[r].tokens[row])
    gaps = check.reference_gaps(ref, dims, server.params, seqs, served,
                                cell.check["reference_block"])
    ok, checks = check.verdict(gaps, cell.check["limits"])
    ref_s = time.perf_counter() - t_ref

    peaks = work.load_peaks(device.device_kind) if traced else None
    run = SimpleNamespace(
        window=window, traffic=tr, dims=dims, setup_s=setup_s,
        peak_bytes=peak, trace=summary, peaks=peaks)
    bench = spec.load_benchmark()
    metrics = {}
    for m in spec.metrics_for(bench, cell.name, traced):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": 1, "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": B * len(window.rounds),
              "failed": 0, "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
        result["roofline_bound"] = summary_bounds(dims, tr, peaks)
    result["setup_parts"] = server.setup_parts
    result["rounds"] = len(window.rounds)
    result["window_s"] = window.end - window.start
    result["compiles_in_window"] = counter.count
    result["reference_s"] = ref_s
    result["checks"] = checks
    return result


def summary_bounds(dims, tr, peaks) -> dict:
    """Which peak bounds each program's needed work."""
    from chipbench import work
    pre = work.prefill_work(dims, tr["batch"], tr["prompt_len"])
    dec = work.decode_step_work(dims, tr["batch"], tr["prompt_len"])
    return {"prefill": work.roofline_s(pre, peaks)[1],
            "decode": work.roofline_s(dec, peaks)[1]}


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result))


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(1, str(ROOT / "src"))
    from chipbench import spec

    cell = spec.load_cell(args.workload)
    jax = configure_jax()
    try:
        devices = find_chips(jax, cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices[0], T0)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
