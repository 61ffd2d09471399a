"""What the program's own stage spans and scopes say about a run.

The server sums every stage span of `elasticsearch_tpu.telemetry.STAGES` into
two counters, `es.span.<stage>.ns` and `es.span.<stage>.count`, which
`_nodes/stats` ships under `metrics.counters` and `run.before` / `run.after`
hold. The leaf stages are also `jax.profiler.TraceAnnotation`s: host events of
a capture, named as the stage, on the capture's clock. Inside the compiled
search program the phases are `jax.named_scope`s (`score`, `topk`, `aggs`).

Which stat carries the scope, read off a v5e capture (PR 26, chip call B):
`tf_op`, the HLO `op_name` metadata, e.g.
`jit(search_solo)/vmap(topk)/jit(_scan_topk_pallas)/pallas_call:`. It is a
stat of the operation's *event metadata* in the device plane (one record an
instruction of a program, beside `hlo_category`, `flops`, `bytes_accessed`,
`source`), not of the event. An `XLA Ops` event itself carries only
`device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`, and they
are all that `jax.profiler.ProfileData` hands out, so `scopes_of` reads the
file's own encoding (XSpace, protobuf wire format) for the few fields it
needs. A program without spans or scopes (the parent of PR 26) gives `None`
or empty dicts everywhere, never an error.

Nothing of `elasticsearch_tpu` is imported here.
"""

from __future__ import annotations

from . import trace

SCOPE_STAT = "tf_op"
SCOPES = ("score", "topk")    # the program also scopes `aggs`; no cell has any
SEARCH = "rest.search"
# `trace.py` lays the gaps against the stages since PR 28 and holds these; the
# names stay here for their readers (`tests/test_stage_spans.py` among them)
MODULES_LINE, STAGE, NO_STAGE = trace.MODULES_LINE, trace.STAGE, trace.NO_STAGE


def _delta(run, key: str):
    before = run.before.get("counters", {}).get(key)
    after = run.after.get("counters", {}).get(key)
    if after is None:
        return None
    return after - (before or 0)


def stage_ms(run, stem: str):
    """Mean milliseconds a search spends in stage `stem` over the window:
    the nanoseconds its spans added between `run.before` and `run.after`,
    divided by the searches that ended in between (`rest.search`'s count:
    the server adds all of one search's stages to the counters at once, and
    nothing of any other endpoint, so every stage's nanoseconds belong to
    those searches, also where a search enters a stage twice). None where
    the stage or the searches did not move."""
    ns = _delta(run, f"es.span.{stem}.ns")
    own = _delta(run, f"es.span.{stem}.count")
    searches = _delta(run, f"es.span.{SEARCH}.count")
    if ns is None or not own or not searches:
        return None
    return ns / searches / 1e6


def scope_of(op_path: str) -> str | None:
    """The outermost of SCOPES on an HLO op_name path; a transformation
    wraps the scope it passes through (`jit(search_solo)/vmap(topk)/...`)."""
    for part in op_path.split("/"):
        inner = part.rsplit("(", 1)[-1].rstrip(")")
        if inner in SCOPES:
            return inner
    return None


def program_of(module_name: str) -> str:
    """`jit_search_solo(1234)` -> `jit_search_solo`."""
    return module_name.split("(", 1)[0]


def _capture_path(run) -> str | None:
    cap_dir = (run.capture.get("started") or {}).get("dir")
    return trace.find_xplane(cap_dir) if cap_dir else None


def _capture(run):
    """The capture of the run, loaded once and kept on it."""
    if getattr(run, "_capture_profile", None) is None:
        path = _capture_path(run)
        run._capture_profile = trace.load(path) if path else False
    return run._capture_profile or None


def device_scopes(run) -> dict:
    """`scopes_of` the run's capture, read once and kept on the run."""
    if getattr(run, "_device_scopes", None) is None:
        path = _capture_path(run)
        if path:
            with open(path, "rb") as f:
                run._device_scopes = scopes_of(f.read())
        else:
            run._device_scopes = scopes_of(b"")
    return run._device_scopes


# -- the capture's own encoding: XSpace in protobuf wire format ---------------
# XSpace.planes = 1; XPlane.name = 2, .lines = 3, .event_metadata = 4 (a map:
# key = 1, value = 2), .stat_metadata = 5 (the same); XLine.name = 2,
# .events = 4; XEvent.metadata_id = 1, .duration_ps = 3; XEventMetadata.name
# = 2, .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
# XStatMetadata.name = 2.

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for anything with a length; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in a capture")


def _first(buf, number: int, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace") if view is not None else ""


def _map(plane_fields, number: int) -> dict:
    """A map field of the plane: key -> the value message."""
    out = {}
    for n, entry in plane_fields:
        if n == number:
            out[_first(entry, 1, 0)] = _first(entry, 2, b"")
    return out


def scopes_of(xspace: bytes) -> dict:
    """-> {"scopes": {scope: s}, "programs": {program: s}, "unscoped_s": s}
    of a serialized capture: seconds of the first device's `XLA Ops` by the
    scope their `tf_op` carries, and of its `XLA Modules` by program name.
    Empty where there is no device plane. On several device planes (a cell on
    four chips) it is still the first plane's alone, one chip's seconds: the
    chips of one sharded program run the same scopes side by side, so this is
    the time a request holds each of them, not their sum."""
    out = {"scopes": {}, "programs": {}, "unscoped_s": 0.0}
    for n, plane in _fields(memoryview(xspace)):
        name = _text(_first(plane, 2)) if n == 1 else ""
        if not name.startswith("/device:") or "CUSTOM" in name.upper():
            continue
        fields = list(_fields(plane))
        stat_names = {k: _text(_first(v, 2))
                      for k, v in _map(fields, 5).items()}
        events = _map(fields, 4)
        scope_by_id, name_by_id = {}, {}
        for key, meta in events.items():
            name_by_id[key] = _text(_first(meta, 2))
            for m, stat in _fields(meta):
                if m != 5 or stat_names.get(_first(stat, 1)) != SCOPE_STAT:
                    continue
                ref = _first(stat, 7)
                path = (stat_names.get(ref, "") if ref is not None
                        else _text(_first(stat, 5)))
                scope_by_id[key] = scope_of(path)
        for m, line in fields:
            if m != 3:
                continue
            line_name = _text(_first(line, 2))
            if line_name not in (trace.OPS_LINE, trace.MODULES_LINE):
                continue
            for k, event in _fields(line):
                if k != 4:
                    continue
                meta_id = _first(event, 1, 0)
                seconds = _first(event, 3, 0) * 1e-12
                if line_name == trace.MODULES_LINE:
                    key = program_of(name_by_id.get(meta_id, ""))
                    out["programs"][key] = (out["programs"].get(key, 0.0)
                                            + seconds)
                elif scope_by_id.get(meta_id):
                    key = scope_by_id[meta_id]
                    out["scopes"][key] = out["scopes"].get(key, 0.0) + seconds
                else:
                    out["unscoped_s"] += seconds
        break
    return out


def scope_ms_per_request(run, scope: str):
    """Device milliseconds of one scope for each request sent and answered
    inside the capture (the requests `postings_roofline` counts)."""
    n = sum(1 for r in run.traced if r.ok)
    seconds = device_scopes(run)["scopes"].get(scope)
    if not n or not seconds:
        return None
    return seconds * 1e3 / n


def idle_by_stage(run, lead_s: float | None = None) -> dict:
    """Seconds of the first device's idle gaps under each leaf stage's
    annotation, and under none (`no stage`), on the host's clock: what
    `breakdown.idle_gaps` gives the driver (`trace.reduce`, `trace.
    idle_by_stage`), from the run's own capture. The device plane's clock is
    set back by `lead_s` first, by default `trace.device_lead`'s estimate (in
    a v5e capture the device plane runs 0.24-1.5 ms ahead of the host planes,
    which left alone moves idle time from the dispatch to the fetch); 0.0
    gives the capture as it stands."""
    profile = _capture(run)
    dev = trace.device_events(profile) if profile is not None else {}
    if not dev:
        return {}
    if lead_s is None:
        lead_s = trace.device_lead(profile)
    gaps = [(g0 + lead_s, g1 + lead_s)
            for g0, g1 in trace.idle_gaps_of(next(iter(dev.values())))]
    return trace.idle_by_stage(gaps, trace.stage_events(profile))
