"""The decode steps' share of the chip's peak FLOP/s: their needed FLOPs
(`work.decode_round_work`) over their device time in the trace times the
peak, in %. The whole step's share, beside `decode_roofline`."""

from chipbench import work


def read(run):
    t = run.trace.decode_s if run.trace else []
    if not t:
        return None
    tr = run.traffic
    rounds = len(t) // tr["decode_steps"]
    flops = work.decode_round_work(run.dims, tr["batch"], tr["prompt_len"],
                                   tr["decode_steps"]).flops
    return (flops * rounds / (sum(t) * run.peaks["bf16_flop_per_s"])
            * 100.0)
