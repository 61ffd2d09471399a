"""Serving front end: mean milliseconds a wave waited between the end of its
launches and the completer thread's start of its fetch
(`engine.wave_handoff`): the depth-1 hand-off and the completer's pick-up,
behind the wave before it. 0 where no wave ended; nothing where the server
ships no such counter."""

from benchlib import waves


def read(run):
    return waves.stage_ms_a_wave(run, "handoff")
