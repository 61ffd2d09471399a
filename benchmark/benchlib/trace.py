"""Reduction of a profiler capture (`*.xplane.pb`, as the server's
`POST /_profiler/start` / `stop` writes it) to device busy time, idle share,
the device operations that took most time and the idle gaps by what the host
was doing in them.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX's own
library and starts no backend. Device planes are those named `/device:...`,
one a chip; within one, the line of XLA operations (`XLA Ops`) is what counts
as an operation running on the device. Module and step lines cover the same
time a second time and are left out.

Two clocks. In a v5e capture the device planes run ahead of the host planes
by 0.24-1.5 ms, set anew in each capture: a program starts on its device plane
before the host plane shows it enqueued. Busy time, span and operations are
device time alone and do not care; the idle gaps are laid against host spans,
and are set back by `device_lead` first.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"   # the runtime's host event that launches a program
# the program's leaf stages, as annotations on the host planes of a capture
# (`jax.profiler.TraceAnnotation`, named as the stage); nothing else is so named
STAGE = re.compile(r"^(engine|rest)\.[a-z_]+$")
NO_STAGE = "no stage"
# host frames that only wait: they cover every gap and explain none
WAITING = ("acquire", "wait", "select", "poll", "sleep", "_bootstrap",
           "run_forever", "_run_once", "_worker", "Thread.run", " run",
           "getresponse", "recv", "readinto", "_read_status", "begin")
SKIP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
              "Framework Name Scope", "Source code")


def find_xplane(capture_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        capture_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def load(path: str):
    from jax.profiler import ProfileData  # imports JAX, starts no backend

    return ProfileData.from_file(path)


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _device_planes(profile):
    return [p for p in profile.planes if p.name.startswith("/device:")
            and "CUSTOM" not in p.name.upper()]


def _op_lines(plane):
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE]
    return ops or [ln for ln in lines if ln.name not in SKIP_LINES]


def op_name(name: str) -> str:
    """The trace names an operation by its whole HLO instruction; keep the
    instruction's own name (`%fusion.3 = f32[...] fusion(...)` -> `fusion.3`)."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]


def device_events(profile) -> dict[str, list[tuple[float, float, str]]]:
    """plane name -> [(start_s, end_s, op name)] of device operations."""
    out = {}
    for plane in _device_planes(profile):
        ev = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
               op_name(e.name))
              for ln in _op_lines(plane) for e in ln.events
              if e.duration_ns > 0]
        if ev:
            out[plane.name] = sorted(ev)
    return out


def host_events(profile, min_s: float = 20e-6):
    """(starts, ends, names) of host spans long enough to explain a gap."""
    starts, ends, names = [], [], []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.duration_ns * 1e-9 >= min_s and not any(
                        w in e.name for w in WAITING):
                    starts.append(e.start_ns * 1e-9)
                    ends.append((e.start_ns + e.duration_ns) * 1e-9)
                    names.append(f"{ln.name or 'thread'}: {e.name}"[:96])
    return np.asarray(starts), np.asarray(ends), names


def name_gap(host, g0: float, g1: float) -> str:
    """What the host was doing in the idle gap [g0, g1): the shortest host
    span that covers nine tenths of it (the innermost frame), else the span
    that overlaps it most."""
    starts, ends, names = host
    if not names:
        return "host: no span recorded"
    ov = np.minimum(ends, g1) - np.maximum(starts, g0)
    covers = np.flatnonzero(ov >= 0.9 * (g1 - g0))
    if covers.size:
        return names[int(covers[np.argmin((ends - starts)[covers])])]
    best = int(np.argmax(ov))
    return names[best] if ov[best] > 0 else "host: no span in the gap"


def idle_gaps_of(events) -> list[tuple[float, float]]:
    """[(start_s, end_s)] of the gaps between one plane's sorted operations."""
    gaps, end = [], None
    for s, e, _ in events:
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def device_lead(profile) -> float:
    """Seconds by which the first device plane's clock runs ahead of the host
    planes': the median, over the host's `DoEnqueueProgram` events, of the
    event's start less the start of the nearest program on the plane's
    `XLA Modules` line (of an even number of readings the upper of the middle
    two). A program cannot start before it is enqueued, so every reading is a
    lower bound of the lead, short by the launch's own latency; the estimate
    is never below 0, and 0.0 where the capture has no such events. Nearest
    means it holds while the lead is under half the time between two programs."""
    modules = np.sort([e.start_ns * 1e-9 for plane in _device_planes(profile)[:1]
                       for ln in plane.lines if ln.name == MODULES_LINE
                       for e in ln.events])
    enqueued = np.asarray([e.start_ns * 1e-9 for plane in profile.planes
                           if plane.name.startswith("/host:")
                           for ln in plane.lines for e in ln.events
                           if e.name == ENQUEUE])
    if not modules.size or not enqueued.size:
        return 0.0
    edges = np.concatenate(([-np.inf], modules, [np.inf]))
    k = np.searchsorted(modules, enqueued)   # modules[k - 1] <= event < modules[k]
    before, after = edges[k], edges[k + 1]
    nearest = np.where(enqueued - before <= after - enqueued, before, after)
    readings = np.sort(enqueued - nearest)
    return max(0.0, float(readings[readings.size // 2]))


def stage_events(profile) -> list[tuple[float, float, str]]:
    """Sorted (start_s, end_s, stage) of the stage annotations on the host
    planes."""
    return sorted(
        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
        for plane in profile.planes if plane.name.startswith("/host:")
        for ln in plane.lines for e in ln.events if STAGE.match(e.name))


def idle_by_stage(gaps, stages) -> dict[str, float]:
    """Seconds of the sorted `gaps` under each of the sorted `stages`, and
    under none (NO_STAGE). A gap is split over every stage that overlaps it;
    stages of two threads that overlap each other both count."""
    out = {NO_STAGE: 0.0}
    i = 0
    for g0, g1 in gaps:
        while i < len(stages) and stages[i][1] <= g0:
            i += 1
        covered = []
        j = i
        while j < len(stages) and stages[j][0] < g1:
            s, e, name = stages[j]
            lo, hi = max(s, g0), min(e, g1)
            if hi > lo:
                out[name] = out.get(name, 0.0) + (hi - lo)
                covered.append((lo, hi))
            j += 1
        out[NO_STAGE] += (g1 - g0) - union_seconds(covered)
    return out


def reduce(profile, top: int = 10, gaps_named: int = 300) -> dict | None:
    """-> {"busy_s", "per_device_busy_s", "span_s", "idle_gap_s",
    "device_lead_s", "device_ops", "idle_gaps"}, or None where no operation
    ran on a device.

    On several device planes (a cell on four chips): `per_device_busy_s` is
    each plane's union of operation intervals, `busy_s` their mean, `span_s`
    runs from the first operation on any plane to the last on any, and
    `device_ops` are seconds a plane (the sum over the planes divided by their
    number). `idle_gap_s` and `idle_gaps` are the first plane's: the chips of
    one sharded program idle together. On one plane all of this is what it was.

    `idle_gaps`: the first plane's gaps, set back onto the host's clock by
    `device_lead_s`, each split over the program's stage annotations that
    overlap it (`idle_by_stage`). A capture without a stage annotation (a
    program older than PR 26, a hand-written plane) has its `gaps_named`
    longest gaps named by the innermost host span instead (`name_gap`), one
    name a gap."""
    dev = device_events(profile)
    if not dev:
        return None
    busy = {name: union_seconds([(s, e) for s, e, _ in ev])
            for name, ev in dev.items()}
    by_op: dict[str, float] = {}
    for ev in dev.values():
        for s, e, name in ev:
            by_op[name] = by_op.get(name, 0.0) + (e - s)
    n = len(dev)
    first = min(ev[0][0] for ev in dev.values())
    last = max(max(e for _, e, _ in ev) for ev in dev.values())
    lead = device_lead(profile)
    raw = idle_gaps_of(next(iter(dev.values())))
    gaps = [(g0 + lead, g1 + lead) for g0, g1 in raw]
    stages = stage_events(profile) if gaps else []
    if stages:
        named = {k: v for k, v in idle_by_stage(gaps, stages).items() if v > 0}
    else:
        host = host_events(profile)
        named = {}
        for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:gaps_named]:
            who = name_gap(host, g0, g1)
            named[who] = named.get(who, 0.0) + (g1 - g0)
    return {
        "busy_s": sum(busy.values()) / n,
        "per_device_busy_s": busy,
        "span_s": last - first,
        "idle_gap_s": sum(g1 - g0 for g0, g1 in raw),
        "device_lead_s": lead,
        "device_ops": [[k, v / n] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
    }
