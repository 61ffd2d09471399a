"""The per-layer reader `engine.dispatch_buffers` (PR 27): host arrays handed
to the compiled program, a search, from the counters `_nodes/stats` ships."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
from checks import BENCH, REPO, harness  # noqa: E402

BUFFERS = "es.search.dispatch.buffers"
SEARCHES = "es.span.rest.search.count"


def _read(before: dict, after: dict):
    run = harness.Run()
    run.before, run.after = {"counters": before}, {"counters": after}
    return harness.layer_reader(BENCH, "engine.dispatch_buffers")(run)


@pytest.mark.parametrize("before, after, want", [
    # the warm-up's dispatches are taken out: 1,500 searches, one buffer each
    ({BUFFERS: 192.0, SEARCHES: 192}, {BUFFERS: 1692.0, SEARCHES: 1692}, 1.0),
    # a window whose queries also carry int64 bounds and bools
    ({BUFFERS: 10.0, SEARCHES: 10}, {BUFFERS: 40.0, SEARCHES: 20}, 3.0),
    # counted from nothing
    ({}, {BUFFERS: 7.0, SEARCHES: 4}, 1.75),
    # every search was a `match_none`: no buffer at all is a reading too
    ({BUFFERS: 5.0, SEARCHES: 5}, {BUFFERS: 5.0, SEARCHES: 9}, 0.0),
])
def test_buffers_a_search_over_the_window(before, after, want):
    assert _read(before, after) == pytest.approx(want)


@pytest.mark.parametrize("before, after", [
    # the parent of this PR: stage counters and no dispatch counter
    ({SEARCHES: 192, "es.span.engine.dispatch.ns": 3_000_000},
     {SEARCHES: 1692, "es.span.engine.dispatch.ns": 2_900_000_000}),
    # a server without stage counters either
    ({"es.jit.compiles": 85.0}, {"es.jit.compiles": 85.0}),
    ({}, {}),
    # no search ended in the window
    ({BUFFERS: 5.0, SEARCHES: 5}, {BUFFERS: 5.0, SEARCHES: 5}),
    ({BUFFERS: 5.0}, {BUFFERS: 9.0}),
])
def test_nothing_to_read_gives_none_and_never_raises(before, after):
    assert _read(before, after) is None


def test_the_metric_is_declared_in_both_cells_under_its_layer():
    # by what the entry says and by who reports it; where it stands in the
    # list, and who else reports it, is the next PR's to change
    checks.check_declared(REPO, checks.DISPATCH_BUFFERS,
                          ["passage.solo.c1", "passage.solo.c8"])
