"""Least time for every decode step's needed work in a Mamba-2/attention
hybrid (`work_hybrid.decode_step_work`: weights, SSM and conv state read
and written, filled KV) at the chip's peaks, summed over the traced
round, over those steps' device time in the trace, in %."""

from chipbench import work, work_hybrid


def read(run):
    t = run.trace.decode_s if run.trace else []
    if not t or "layer_types" not in run.dims:
        return None
    tr = run.traffic
    if len(t) % tr["decode_steps"]:
        raise ValueError(f"{len(t)} decode executions in the trace, "
                         f"not a multiple of {tr['decode_steps']}")
    rounds = len(t) // tr["decode_steps"]
    need = sum(work.roofline_s(
        work_hybrid.decode_step_work(run.dims, tr["batch"],
                                     tr["prompt_len"] + i),
        run.peaks)[0] for i in range(tr["decode_steps"]))
    return need * rounds / sum(t) * 100.0
