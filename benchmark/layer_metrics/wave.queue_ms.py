"""Serving front end: mean milliseconds a wave waited between its claim and
the engine thread's start of its planning (`engine.wave_queue`): the
scheduler's hand-off to the engine thread and that thread's other work. 0
where no wave ended; nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.stage_ms_a_wave(run, "queue")
