"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

`load` keeps three kinds of event, on one clock:

- ``op``: an operation executing on the device (the device plane's
  ``XLA Ops`` line; a ``while`` op encloses the ops of its body);
- ``module``: one execution of a compiled program on the device (the
  ``XLA Modules`` line), named after the jitted function;
- ``span``: the benchmark's own host spans (`harness.SPANS`).

`summarize` reduces them over the traced window, which is the host span
``round``: busy is the union of the ops' intervals; the top ops are ranked
by self time; each idle interval is put down to the program the device
was inside, or else to the innermost benchmark span the host was in."""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclass(frozen=True)
class Event:
    kind: str      # "op", "module" or "span"
    name: str
    start: float   # seconds
    end: float


@dataclass
class Summary:
    window_s: float
    busy_s: float
    prefill_s: list = field(default_factory=list)   # per execution
    decode_s: list = field(default_factory=list)
    decode_gaps_s: list = field(default_factory=list)
    top_ops: list = field(default_factory=list)     # [[name, s], ...]
    idle_gaps: list = field(default_factory=list)   # [[span, s], ...]


def events_of(profile, spans) -> list[Event]:
    """Events of a `jax.profiler.ProfileData`, device plane 0 only."""
    out = []
    device = sorted(p.name for p in profile.planes
                    if p.name.startswith(DEVICE_PLANE))
    for plane in profile.planes:
        if device and plane.name == device[0]:
            kinds = {OPS_LINE: "op", MODULES_LINE: "module"}
            for line in plane.lines:
                kind = kinds.get(line.name)
                if kind:
                    out += [Event(kind, e.name, e.start_ns * 1e-9,
                                  e.end_ns * 1e-9) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out += [Event("span", e.name, e.start_ns * 1e-9,
                              e.end_ns * 1e-9)
                        for e in line.events if e.name in spans]
    return out


def load(tdir: Path, spans) -> list[Event]:
    """Events of the one ``.xplane.pb`` the profiler wrote under ``tdir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(tdir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {tdir}, "
                         f"found {len(files)}")
    return events_of(ProfileData.from_file(str(files[0])), spans)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def idle_intervals(merged, lo: float, hi: float):
    """The parts of [lo, hi] that the merged intervals leave uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``;
    ``jit_serve_step(123)`` -> ``serve_step``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    if name.startswith("jit_"):
        name = name[len("jit_"):].split("(", 1)[0]
    return name


def self_times(ops: list[Event]) -> dict[str, float]:
    """Each op's time less that of the ops nested inside it (a ``while``
    encloses its body's ops on the same line), summed by short name."""
    out: dict[str, float] = defaultdict(float)
    stack: list[Event] = []
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and e.end > stack[-1].end:
            stack.pop()
        if stack:
            out[short_name(stack[-1].name)] -= e.end - e.start
        out[short_name(e.name)] += e.end - e.start
        stack.append(e)
    return out


def _label(spans: list[Event], mods: list[Event], t: float) -> str:
    """What the device was inside, or else the innermost host span open at
    time ``t`` (the latest to start)."""
    for m in mods:
        if m.start <= t <= m.end:
            return f"inside {short_name(m.name)}"
    open_ = [s for s in spans if s.start <= t <= s.end]
    return max(open_, key=lambda s: s.start).name if open_ else "no span"


def summarize(events: list[Event], prefill: str, decode: str) -> Summary:
    rounds = [e for e in events if e.kind == "span" and e.name == "round"]
    if not rounds:
        raise ValueError("no 'round' span in the trace")
    lo = min(r.start for r in rounds)
    hi = max(r.end for r in rounds)
    ops = [Event(e.kind, e.name, max(e.start, lo), min(e.end, hi))
           for e in events if e.kind == "op" and e.end > lo and e.start < hi]
    busy = union((e.start, e.end) for e in ops)
    # by overlap, not start: the device clock is mapped onto the host's,
    # and a prefill that starts within a millisecond of the round can map
    # to just before it
    mods = sorted((e for e in events if e.kind == "module"
                   and e.end > lo and e.start < hi), key=lambda e: e.start)
    pre = [e for e in mods if prefill in e.name]
    dec = [e for e in mods if decode in e.name]
    gaps = [(b.start - a.end) - covered(busy, a.end, b.start)
            for a, b in zip(dec, dec[1:])]

    top_ops = sorted(self_times(ops).items(), key=lambda kv: -kv[1])[:TOP]

    spans = [e for e in events if e.kind == "span"]
    per_span: dict[str, float] = defaultdict(float)
    count: dict[str, int] = defaultdict(int)
    for s, e in idle_intervals(busy, lo, hi):
        name = _label(spans, mods, (s + e) / 2)
        per_span[name] += e - s
        count[name] += 1
    idle = sorted(per_span.items(), key=lambda kv: -kv[1])[:TOP]

    return Summary(
        window_s=hi - lo,
        busy_s=sum(e - s for s, e in busy),
        prefill_s=[e.end - e.start for e in pre],
        decode_s=[e.end - e.start for e in dec],
        decode_gaps_s=gaps,
        top_ops=[[n, t] for n, t in top_ops],
        idle_gaps=[[f"{n} x{count[n]}", t] for n, t in idle])
