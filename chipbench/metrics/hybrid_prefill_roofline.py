"""Least time for the prefill's needed work in a Mamba-2/attention hybrid
(`work_hybrid.prefill_work`) at the chip's peaks, over the prefill
program's device time in the trace, in %."""

from chipbench import work, work_hybrid


def read(run):
    t = run.trace.prefill_s if run.trace else []
    if not t or "layer_types" not in run.dims:
        return None
    tr = run.traffic
    need = work_hybrid.prefill_work(run.dims, tr["batch"], tr["prompt_len"])
    return work.roofline_s(need, run.peaks)[0] * len(t) / sum(t) * 100.0
