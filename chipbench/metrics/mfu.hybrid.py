"""Needed FLOPs of every prefill and decode step of a Mamba-2/attention
hybrid in the traced window (`work_hybrid`), over the window's length
times the chip's peak FLOP/s, in %: the whole step's share of the peak."""

from chipbench import work_hybrid


def read(run):
    if not run.trace or not run.trace.window_s \
            or "layer_types" not in run.dims:
        return None
    tr = run.traffic
    n_pre = len(run.trace.prefill_s)
    n_dec = len(run.trace.decode_s) // tr["decode_steps"]
    flops = (n_pre * work_hybrid.prefill_work(run.dims, tr["batch"],
                                              tr["prompt_len"]).flops
             + n_dec * work_hybrid.decode_round_work(
                 run.dims, tr["batch"], tr["prompt_len"],
                 tr["decode_steps"]).flops)
    return flops / (run.trace.window_s * run.peaks["bf16_flop_per_s"]) * 100.0
