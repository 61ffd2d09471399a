"""Host planning, dispatch and fetch: milliseconds a search spends handing
the rest of its work to the completer thread (span `engine.handoff`: from its
launch's end to another thread's taking the engine, the wait for the completer
to finish the search before it included), over every search of the window. It
lies inside `engine.unnamed_ms`, whose reader does not know it. 0 where no
search handed over; nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, ["es.span.engine.handoff.ns"],
                      "es.span.rest.search.count", 1e-6)
