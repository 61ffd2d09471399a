"""Host planning, dispatch and fetch: mean milliseconds of the engine thread a
wave, over the waves that ended in the window: the stages `engine.wave_plan`
(the members' parsing and the batch plan, less the launches inside it),
`engine.wave_launch` (the calls of the compiled programs) and
`engine.wave_finish` (escalations and the answers). 0 where no wave ended;
nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    return waves.stage_ms_a_wave(run, "plan", "launch", "finish")
