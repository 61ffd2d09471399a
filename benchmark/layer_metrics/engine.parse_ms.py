"""Host planning, dispatch and fetch: milliseconds of a search spent parsing
the query DSL into nodes (`parse_query`, `parse_aggs`). Span `engine.parse`:
its nanoseconds over the searches of the window."""

from benchlib import spans


def read(run):
    return spans.stage_ms(run, "engine.parse")
