"""The decode step's device time by part, and the decode gap by part, from
a traced round: what the program names inside itself, read beside what
`trace.py` reads.

- Parts of a decode step. The step programs open `jax.named_scope` at each
  sublayer (``repro.models.transformer``): ``layers``, ``attention``,
  ``kv_cache`` (inside ``attention``), ``mlp``, ``lm_head``. XLA keeps the
  scope in each compiled instruction's ``op_name``. The profiler stores it
  as ``tf_op`` on the op's event metadata, which
  ``jax.profiler.ProfileData`` does not expose, so an op's scope is read
  from the decode executable's own HLO text (`op_names`), by the
  instruction name the trace gives the op. An op belongs to the innermost
  scope in its ``op_name``; ``layers`` and no sublayer is ``scan`` (the
  layer scan's carry, its slicing and stacking of the cache, the ``while``
  op's own time); no scope at all is ``unscoped`` (the embedding, and what
  XLA adds with no metadata). The six parts partition the op self time of
  the decode executions.
- Parts of a decode gap. The serving loop's turns are host spans
  ``serve.step`` (``repro.launch.serve.decode_tokens``). Between decode
  executions k and k+1, ``readback`` runs from the device end of k to the
  end of turn k (the wait for the token, and the host waking), ``launch``
  from there to the device start of k+1 (the loop's turn, dispatch and
  launch). They sum to the interval, and neither is clamped: a negative
  mean says the two clocks disagree.

A trace of a program without scopes or turns gives no numbers (None).

    python3 chipbench/parts.py --workload granite-3-2b.decode --seed 7

sets up one cell on the chip, serves one round untraced and the same round
traced, and prints one JSON object: the eight numbers, the decode ops by
part, and the per-token host-clock gaps of both rounds."""

from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402

SCOPES = ("layers", "attention", "kv_cache", "mlp", "lm_head")
PARTS = ("attention", "kv_cache", "mlp", "lm_head", "scan", "unscoped")
TURN = "serve.step"

_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*?op_name=\"([^\"]*)\"",
                    re.M)


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction that has one.
    XLA joins the names of merged ops with ``;``; the first is the full
    path."""
    return {m.group(1): m.group(2).split(";", 1)[0]
            for m in _INSTR.finditer(hlo_text)}


def part_of(op_name: str | None) -> str:
    """The part an op with this ``op_name`` belongs to."""
    inner = [c for c in (op_name or "").split("/") if c in SCOPES]
    if not inner:
        return "unscoped"
    return "scan" if inner[-1] == "layers" else inner[-1]


def self_time_events(ops: list) -> list:
    """(op, self time) pairs: each op's time less that of the ops nested
    inside it, as `trace.self_times` counts it."""
    out: list = []
    stack: list = []      # indices into out
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and e.end > out[stack[-1]][0].end:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= e.end - e.start
        out.append([e, e.end - e.start])
        stack.append(len(out) - 1)
    return [(e, t) for e, t in out]


@dataclass
class Parts:
    n_decode: int = 0
    decode_s: dict = field(default_factory=dict)    # part -> s, all steps
    decode_ops: dict = field(default_factory=dict)  # part -> {op: s}
    readback_s: list = field(default_factory=list)  # per gap
    launch_s: list = field(default_factory=list)
    # device start of step k less the start of turn k, which dispatched
    # it: positive on one clock, so a negative value says by how much at
    # least the device clock maps early onto the host's
    lead_s: list = field(default_factory=list)


def reduce(events: list, names: dict[str, str], decode: str) -> Parts:
    """Parts of the decode executions in the traced round (the host span
    ``round``, as `trace.summarize` takes it). ``events`` come from
    `trace.events_of` with `TURN` among the spans; ``names`` from
    `op_names` of the decode executable."""
    rounds = [e for e in events if e.kind == "span" and e.name == "round"]
    if not rounds:
        raise ValueError("no 'round' span in the trace")
    lo = min(r.start for r in rounds)
    hi = max(r.end for r in rounds)
    dec = sorted((e for e in events if e.kind == "module"
                  and decode in e.name and e.end > lo and e.start < hi),
                 key=lambda e: e.start)
    parts = Parts(n_decode=len(dec))
    if not dec:
        return parts

    ops = [trace.Event(e.kind, e.name, max(e.start, lo), min(e.end, hi))
           for e in events if e.kind == "op" and e.end > lo and e.start < hi]
    starts = [m.start for m in dec]
    by_part: dict = defaultdict(lambda: defaultdict(float))
    for e, t in self_time_events(ops):
        mid = (e.start + e.end) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i < 0 or mid > dec[i].end:
            continue                  # not inside a decode execution
        name = trace.short_name(e.name)
        by_part[part_of(names.get(name))][name] += t
    if any(p != "unscoped" for p in by_part):
        parts.decode_ops = {p: dict(by_part[p]) for p in PARTS}
        parts.decode_s = {p: sum(by_part[p].values()) for p in PARTS}

    turns = sorted((e for e in events if e.kind == "span" and e.name == TURN
                    and e.end > lo and e.start < hi), key=lambda e: e.start)
    if turns:
        if len(turns) != len(dec):
            raise ValueError(f"{len(turns)} {TURN} spans in the round, "
                             f"{len(dec)} decode executions")
        for a, t, b in zip(dec, turns, dec[1:]):
            parts.readback_s.append(t.end - a.end)
            parts.launch_s.append(b.start - t.end)
        parts.lead_s = [a.start - t.start for a, t in zip(dec, turns)]
    return parts


def _mean_ms(xs) -> float | None:
    return sum(xs) / len(xs) * 1e3 if xs else None


def metrics(parts: Parts) -> dict:
    """The eight numbers, in ms; a number with nothing to read is left
    out."""
    out = {}
    if parts.decode_s:
        for p in PARTS:
            out[f"decode_ms.{p}"] = parts.decode_s[p] / parts.n_decode * 1e3
    for key, gaps in (("decode_gap.readback_ms", parts.readback_s),
                      ("decode_gap.launch_ms", parts.launch_s)):
        if gaps:
            out[key] = _mean_ms(gaps)
    return out


def _gaps_ms(deliveries) -> dict:
    """Host-clock gaps between consecutive decode tokens, in ms."""
    import numpy as np
    d = np.diff(np.asarray(deliveries)[1:]) * 1e3
    return {"mean": float(d.mean()), "median": float(np.median(d)),
            "p95": float(np.percentile(d, 95))}


def _min_median_ms(xs) -> list | None:
    return [min(xs) * 1e3, sorted(xs)[len(xs) // 2] * 1e3] if xs else None


def traced_parts(server, tdir: Path) -> dict:
    """Serve round 0 untraced, then again under the profiler (so both have
    the same seed and tokens), and reduce the traced one."""
    import shutil
    import time

    import jax

    from chipbench import harness

    plain = server.serve_round(server.prompts(0), time.perf_counter())
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    time.sleep(1.0)   # let the device tracer start before the round
    try:
        traced = server.serve_round(server.prompts(0), time.perf_counter())
    finally:
        jax.profiler.stop_trace()
    events = trace.load(tdir, harness.SPANS + (TURN,))
    shutil.rmtree(tdir, ignore_errors=True)
    summary = trace.summarize([e for e in events if e.name != TURN],
                              prefill="prefill_step", decode="serve_step")
    parts = reduce(events, op_names(server.decode.as_text()), "serve_step")
    top = {p: sorted(((n, t / parts.n_decode * 1e3) for n, t in ops.items()),
                     key=lambda kv: -kv[1])[:8]
           for p, ops in parts.decode_ops.items()}
    return {
        "metrics": metrics(parts),
        "decode_step_ms": _mean_ms(summary.decode_s),
        "decode_gap_ms": _mean_ms(summary.decode_gaps_s),
        "decode_ops_ms_per_step": top,
        "min_median_ms": {"lead": _min_median_ms(parts.lead_s),
                          "readback": _min_median_ms(parts.readback_s),
                          "launch": _min_median_ms(parts.launch_s)},
        "token_gap_ms": {"untraced": _gaps_ms(plain.deliveries),
                         "traced": _gaps_ms(traced.deliveries)},
        "same_tokens": bool((plain.tokens == traced.tokens).all())}


def main(argv=None) -> int:
    import argparse
    import json

    from chipbench import harness, run, spec
    from chipbench import traffic as traffic_lib

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(1, str(run.ROOT / "src"))
    from repro.configs import get_config

    cell = spec.load_cell(args.workload)
    jax = run.configure_jax()
    try:
        device = run.find_chips(jax, cell.chips)[0]
    except run.NoChip as e:
        print(f"parts: {e}; no result", file=sys.stderr)
        return 2
    dims = spec.reference(cell.config["reference"]).dims(cell.config)
    cfg = get_config(cell.config["arch"])
    run.check_program_config(cfg, dims)
    server = harness.set_up(cfg, traffic_lib.validate(cell.traffic),
                            args.seed, dims["vocab"], device)
    out = traced_parts(server, run.CACHE / "trace" / f"{cell.name}.parts")
    print(json.dumps({"workload": cell.name, "seed": args.seed, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
