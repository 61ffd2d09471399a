"""Host planning, dispatch and fetch: milliseconds a search waits behind the
one engine thread, from the handler handing it to the pool to the thread
picking it up (span `engine.queue`, recorded for searches alone)."""

from benchlib import spans


def read(run):
    return spans.stage_ms(run, "engine.queue")
