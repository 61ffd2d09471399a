"""REST front end: milliseconds of a search between the engine's result and
the response object: fetch phase, `_source` filtering, `_shards`, JSON
encoding (span `rest.respond`, a part of `rest.server_ms`)."""

from benchlib import spans


def read(run):
    return spans.stage_ms(run, "rest.respond")
