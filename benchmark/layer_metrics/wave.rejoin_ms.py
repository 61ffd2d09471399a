"""Serving front end: mean milliseconds a wave waited between the end of its
fetch and the engine thread's start of its finish (`engine.wave_rejoin`):
the completer's hand-back and the engine thread's other work. 0 where no wave
ended; nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.stage_ms_a_wave(run, "rejoin")
