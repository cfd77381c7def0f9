"""Median over every request in the window of the time from the round's
start, when its requests were due, to the delivery of the prefill's token
(host clock)."""

import statistics


def read(run):
    ttft = []
    for r in run.window.rounds:
        ttft += [r.deliveries[0] - r.start] * r.tokens.shape[0]
    return statistics.median(ttft) * 1e3
