"""Mean device-idle time between consecutive executions of the decode step
in the traced round: the serving loop's per-token cost (dispatch, token
read-back, the host's turn-around)."""


def read(run):
    gaps = run.trace.decode_gaps_s if run.trace else []
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3
