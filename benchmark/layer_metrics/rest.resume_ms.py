"""REST front end: milliseconds from a search's answer being ready (the end
of its engine-thread call, or its wave resolving its future) to its handler's
next statement (span `rest.resume`): the event loop's wake-up and its queue
of other callbacks. 0 where no search ended; nothing where the server ships
no such counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, ["es.span.rest.resume.ns"],
                      "es.span.rest.search.count", 1e-6)
