"""REST front end: mean milliseconds from a connection's last answer (aiohttp
has written it) to its next request reaching the server's first middleware
(`es.rest.client_gap.ns` over `.count`, every request that follows an answer
on its connection): the client's turnaround, the socket, aiohttp's parsing
and the event loop's delay in starting the handler. 0 where no connection
carried a second request; nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, ["es.rest.client_gap.ns"],
                      "es.rest.client_gap.count", 1e-6)
