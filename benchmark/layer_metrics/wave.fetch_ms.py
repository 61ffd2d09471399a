"""Host planning, dispatch and fetch: mean milliseconds a wave's one combined
`device_get` took on the completer thread (`engine.wave_fetch`): the wait for
the wave's programs and the copy of their rows. 0 where no wave ended; nothing
where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.stage_ms_a_wave(run, "fetch")
