"""Needed FLOPs of every prefill and decode step in the traced window, over
the window's length times the chip's peak FLOP/s, in %."""

from chipbench import work


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    tr = run.traffic
    n_pre = len(run.trace.prefill_s)
    n_dec = len(run.trace.decode_s) // tr["decode_steps"]
    flops = (n_pre * work.prefill_work(run.dims, tr["batch"],
                                       tr["prompt_len"]).flops
             + n_dec * work.decode_round_work(run.dims, tr["batch"],
                                              tr["prompt_len"],
                                              tr["decode_steps"]).flops)
    return flops / (run.trace.window_s * run.peaks["bf16_flop_per_s"]) * 100.0
