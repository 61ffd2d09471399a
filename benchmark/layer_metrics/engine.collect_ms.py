"""Host planning, dispatch and fetch: milliseconds of a search spent making
the fetched rows into a result and its hits (`_agg_finalize`,
`_format_generic_hits`). Span `engine.collect`, entered twice a search: its
nanoseconds over the searches of the window."""

from benchlib import spans


def read(run):
    return spans.stage_ms(run, "engine.collect")
