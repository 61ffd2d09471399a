"""Serving front end: mean milliseconds a member waited between admission and
the claim by its wave (`es.serving.wave.wait_ns` over
`es.serving.wave.members`): the coalescing window, and the scheduler's waits
for the engine thread and for the completer. 0 where no wave ended; nothing
where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, [waves.WAIT_NS], waves.MEMBERS, 1e-6)
