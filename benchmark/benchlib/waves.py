"""What the serving front end's own counters say about a window's waves.

A wave (`elasticsearch_tpu/serving/service.py`) adds to `_nodes/stats` ->
`metrics.counters` at once, when it ends: 1 to `es.serving.wave.count`, its
members to `es.serving.wave.members`, the rows its programs computed for them
(a term lane's batch tier; one for any other member) to
`es.serving.wave.padded_rows`, the nanoseconds its members waited between
admission and the claim to `es.serving.wave.wait_ns`, and its four stages to
`es.span.engine.wave_<stage>.ns` / `.count` (`plan`: host planning on the
engine thread less the launches inside it; `launch`: the calls of the compiled
programs; `fetch`: the one combined `device_get` on the completer thread;
`finish`: the answers, on the engine thread). The program ships every one of
them from its start, at 0.

A metric without a `workloads` list is reported by every cell, so a reader
says what is true where the front end is off as well: no wave ended, and
nothing was spent on one (0). It gives `None` only where the server ships no
such counter: a program from before the front end had them (the parent of PR
35).

Nothing of `elasticsearch_tpu` is imported here.
"""

from __future__ import annotations

from . import spans

WAVES = "es.serving.wave.count"
MEMBERS = "es.serving.wave.members"
ROWS = "es.serving.wave.padded_rows"
WAIT_NS = "es.serving.wave.wait_ns"
# every look-up of a wave program's cache adds a hit or a miss here
PROGRAM_MISSES = "es.jit.cache.wave_program.misses"

# what the window added to a counter; None where the server ships none
added = spans._delta


def mean(run, numerators: list[str], denominator: str, scale: float = 1.0):
    """`scale` x what the window added to the `numerators`, summed, over
    what it added to the `denominator`; 0 where the denominator did not
    move; None where the server ships one of the counters not at all."""
    num = [added(run, key) for key in numerators]
    den = added(run, denominator)
    if den is None or any(v is None for v in num):
        return None
    return scale * sum(num) / den if den else 0.0


def stage_ms_a_wave(run, *stages: str):
    """Mean milliseconds a wave of the window spent in the given stages
    (`plan`, `launch`, `fetch`, `finish`), summed."""
    return mean(run, [f"es.span.engine.wave_{s}.ns" for s in stages], WAVES,
                1e-6)
