"""The prefill program's share of the chip's peak FLOP/s: its needed FLOPs
(`work.prefill_work`) over its device time in the trace times the peak,
in %. The whole step's share, beside `prefill_roofline`."""

from chipbench import work


def read(run):
    t = run.trace.prefill_s if run.trace else []
    if not t:
        return None
    tr = run.traffic
    flops = work.prefill_work(run.dims, tr["batch"], tr["prompt_len"]).flops
    return (flops * len(t) / (sum(t) * run.peaks["bf16_flop_per_s"])
            * 100.0)
