"""Output tokens delivered in the window, over the window (host clock)."""


def read(run):
    w = run.window
    tokens = sum(r.tokens.size for r in w.rounds)
    return tokens / (w.end - w.start)
