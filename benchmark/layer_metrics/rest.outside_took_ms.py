"""REST front end: mean client latency minus mean `took`, over the untraced
requests of the window. `took` is whole milliseconds, truncated, so the
difference reads up to 1 ms high."""


def read(run):
    rs = [r for r in run.untraced if r.ok and r.took_ms is not None]
    if not rs:
        return None
    return (sum(r.latency_ms for r in rs) - sum(r.took_ms for r in rs)) / len(rs)
