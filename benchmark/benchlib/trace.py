"""Reduction of a profiler capture (`*.xplane.pb`, as the server's
`POST /_profiler/start` / `stop` writes it) to device busy time, idle share,
the device operations that took most time and the longest idle gaps.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX's own
library and starts no backend. Device planes are those named `/device:...`;
within one, the line of XLA operations (`XLA Ops`) is what counts as an
operation running on the device. Module and step lines cover the same time a
second time and are left out.
"""

from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
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


def reduce(profile, top: int = 10, gaps_named: int = 300) -> dict | None:
    """-> {"busy_s" (mean over device planes), "per_device_busy_s",
    "span_s" (first to last device operation), "device_ops", "idle_gaps"},
    or None where no operation ran on a device. `idle_gaps` sums the
    `gaps_named` longest gaps of the first device by what the host was doing
    in each."""
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
    gaps, end = [], None
    for s, e, _ in next(iter(dev.values())):
        if end is not None and s > end:
            gaps.append((s - end, end, s))
        end = e if end is None else max(end, e)
    gaps.sort(reverse=True)
    host = host_events(profile) if gaps else None
    named: dict[str, float] = {}
    for length, g0, g1 in gaps[:gaps_named]:
        who = name_gap(host, g0, g1)
        named[who] = named.get(who, 0.0) + length
    return {
        "busy_s": sum(busy.values()) / n,
        "per_device_busy_s": busy,
        "span_s": last - first,
        "idle_gap_s": sum(g[0] for g in gaps),
        "device_ops": [[k, v / n] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
    }
