"""Device: 1 - (union of device-operation intervals / traced span), in %.
Both are on the trace's own clock: the span runs from the first device
operation of the capture to its last, under a load that never pauses."""


def read(run):
    if run.trace is None or run.trace["span_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["span_s"])
