"""REST front end: milliseconds a search's handler spends reading its body
and decoding the JSON (span `rest.read`). 0 where no search ended; nothing
where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, ["es.span.rest.read.ns"],
                      "es.span.rest.search.count", 1e-6)
