"""Host planning, dispatch and fetch: milliseconds of a search spent in
`jax.device_get` of the program's outputs, which waits for the device. Span
`engine.fetch`: its nanoseconds over the searches of the window."""

from benchlib import spans


def read(run):
    return spans.stage_ms(run, "engine.fetch")
