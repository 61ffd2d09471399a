"""Host planning, dispatch and fetch: milliseconds of a search spent on shard
views, `node.prepare`, stacking the parameters and the look-up of the
compiled program. Span `engine.plan`: its nanoseconds over the searches of
the window."""

from benchlib import spans


def read(run):
    return spans.stage_ms(run, "engine.plan")
