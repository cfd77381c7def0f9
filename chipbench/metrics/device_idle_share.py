"""One minus the union of the intervals in which an operation ran on the
device, over the traced window, in %."""


def read(run):
    if not run.trace or not run.trace.window_s:
        return None
    return (1.0 - run.trace.busy_s / run.trace.window_s) * 100.0
