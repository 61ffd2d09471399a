"""Host planning, dispatch and fetch: the share of the window's searches, in
per cent, whose fetch and what follows it ran on the completer thread while
the engine thread planned and launched the next search
(`es.search.solo.handed_off` over `es.span.rest.search.count`). A search hands
over only while another waits for the engine thread: near 100 where the
clients keep it busy, 0 under one client and where no solo search ran (the
serving cell); nothing where the server ships no such counter (a program from
before the completer)."""

from benchlib import waves

HANDED_OFF = "es.search.solo.handed_off"


def read(run):
    share = waves.mean(run, [HANDED_OFF], "es.span.rest.search.count")
    return None if share is None else 100.0 * share
