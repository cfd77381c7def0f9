"""95th percentile over every request in the window of its time per
output token after the first: from the delivery of its first token to
that of its last, over the tokens between (host clock). Each reading
spans a whole decode, never a single step."""

import statistics


def read(run):
    tpot = []
    for r in run.window.rounds:
        n = len(r.deliveries) - 1
        tpot += [(r.deliveries[-1] - r.deliveries[0]) / n] * r.tokens.shape[0]
    if len(tpot) < 2:
        return tpot[0] * 1e3
    return statistics.quantiles(tpot, n=20, method="inclusive")[-1] * 1e3
