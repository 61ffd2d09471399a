"""Host planning, dispatch and fetch: milliseconds a search spends on the
engine thread outside its five stages (span `engine.search` less `engine.parse`,
`plan`, `dispatch`, `fetch` and `collect`): index resolution, the refresh check,
device recovery, the slow log, `time_kernel`'s cost-model arithmetic."""

from benchlib import spans

CHILDREN = ("engine.parse", "engine.plan", "engine.dispatch", "engine.fetch",
            "engine.collect")


def read(run):
    whole = spans.stage_ms(run, "engine.search")
    parts = [spans.stage_ms(run, c) for c in CHILDREN]
    if whole is None or any(p is None for p in parts):
        return None
    return whole - sum(parts)
