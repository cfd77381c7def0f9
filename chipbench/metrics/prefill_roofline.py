"""Least time for the prefill's needed work (`work.prefill_work`) at the
chip's peaks, over the prefill program's device time in the trace, in %."""

from chipbench import work


def read(run):
    t = run.trace.prefill_s if run.trace else []
    if not t:
        return None
    tr = run.traffic
    need = work.prefill_work(run.dims, tr["batch"], tr["prompt_len"])
    return work.roofline_s(need, run.peaks)[0] * len(t) / sum(t) * 100.0
