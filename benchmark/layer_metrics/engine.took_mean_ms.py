"""Host planning, dispatch and fetch: mean `took` of the untraced requests of
the window (whole milliseconds each, truncated by the server)."""


def read(run):
    rs = [r.took_ms for r in run.untraced if r.ok and r.took_ms is not None]
    return sum(rs) / len(rs) if rs else None
