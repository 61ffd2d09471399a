"""Host planning, dispatch and fetch: milliseconds of a search spent calling
the compiled program: argument transfer and launch. Span `engine.dispatch`:
its nanoseconds over the searches of the window."""

from benchlib import spans


def read(run):
    return spans.stage_ms(run, "engine.dispatch")
