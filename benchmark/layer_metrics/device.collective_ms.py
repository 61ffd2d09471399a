"""Kernels: device milliseconds a request spends in collectives, on the first
device plane of the capture: operations named `all-gather*`, `all-reduce*` or
`collective-permute*` (the merge of the shards' top hits across the chips),
over the requests sent and answered inside the capture. One chip's time a
request, as `device.topk_ms`. A cell on one chip runs no collective and reads
0; nothing where the capture has no device plane."""

from benchlib import spans, trace

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute")


def collective_seconds(events) -> float:
    """Seconds of [(start_s, end_s, op name)] whose operation is a collective."""
    return sum(e - s for s, e, name in events if name.startswith(COLLECTIVES))


def read(run):
    n = sum(1 for r in run.traced if r.ok)
    profile = spans._capture(run)
    planes = trace.device_events(profile) if profile is not None else {}
    if not n or not planes:
        return None
    return collective_seconds(next(iter(planes.values()))) * 1e3 / n
