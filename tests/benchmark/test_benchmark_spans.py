"""benchlib/spans.py and the per-layer readers built on it (PR 26): the stage
counters of a stub run, and the scopes, programs and idle split of a
hand-written capture and of one recorded on the chip."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402
from benchlib import spans, trace  # noqa: E402
from benchlib.stats import Request  # noqa: E402

ENGINE_STAGES = ("parse", "plan", "dispatch", "fetch", "collect")


def _run(before: dict, after: dict):
    run = harness.Run()
    run.before, run.after = {"counters": before}, {"counters": after}
    return run


def _counters(**stages):
    """stage -> (ns, count) as the counters `_nodes/stats` ships."""
    out = {}
    for stem, (ns, count) in stages.items():
        stem = stem.replace("_", ".")
        out[f"es.span.{stem}.ns"] = ns
        out[f"es.span.{stem}.count"] = count
    return out


def _read(name: str, run):
    return harness.layer_reader(BENCH, name)(run)


# -- the span counters --------------------------------------------------------

@pytest.mark.parametrize("metric", [f"engine.{s}_ms" for s in ENGINE_STAGES]
                         + ["engine.queue_ms", "rest.respond_ms"])
def test_a_stage_reads_its_nanoseconds_over_the_searches_of_the_window(metric):
    stage = metric[:-len("_ms")].replace(".", "_")
    before = _counters(rest_search=(9_000_000, 3), **{stage: (1_000_000, 3)})
    # four more searches; the stage ran twice in each of them
    after = _counters(rest_search=(29_000_000, 7), **{stage: (7_000_000, 11)})
    assert _read(metric, _run(before, after)) == pytest.approx(1.5)


@pytest.mark.parametrize("metric", [f"engine.{s}_ms" for s in ENGINE_STAGES]
                         + ["engine.queue_ms", "rest.respond_ms"])
def test_a_stage_whose_count_did_not_move_or_is_missing_reads_nothing(metric):
    stage = metric[:-len("_ms")].replace(".", "_")
    same = _counters(rest_search=(9_000_000, 3), **{stage: (1_000_000, 3)})
    assert _read(metric, _run(same, dict(same))) is None
    assert _read(metric, _run({}, {})) is None
    # the searches moved and the stage did not, and the other way round
    assert _read(metric, _run(same, {**same, **_counters(
        rest_search=(12_000_000, 4))})) is None
    assert _read(metric, _run(same, {**same, **_counters(
        **{stage: (2_000_000, 4)})})) is None
    # a parent of this PR ships no such counter at all
    assert _read(metric, _run({"es.jit.compiles": 3.0},
                              {"es.jit.compiles": 3.0})) is None


def test_the_queue_counts_from_nothing():
    after = _counters(rest_search=(90_000_000, 2), engine_queue=(80_000_000, 2))
    assert _read("engine.queue_ms", _run({}, after)) == pytest.approx(40.0)


def test_unattributed_is_the_search_less_its_five_stages():
    before = _counters(rest_search=(0, 0), engine_search=(0, 0))
    after = _counters(rest_search=(11_000_000, 2),
                      engine_search=(10_000_000, 2), engine_parse=(200_000, 2),
                      engine_plan=(2_000_000, 2), engine_dispatch=(1_000_000, 2),
                      engine_fetch=(4_000_000, 2), engine_collect=(400_000, 4))
    run = _run(before, after)
    assert _read("engine.unattributed_ms", run) == pytest.approx(5.0 - 3.8)
    del after["es.span.engine.collect.ns"], after["es.span.engine.collect.count"]
    assert _read("engine.unattributed_ms", _run(before, after)) is None


def test_the_server_share_is_the_handler_less_queue_and_engine():
    after = _counters(rest_search=(12_000_000, 2), engine_queue=(1_000_000, 2),
                      engine_search=(10_000_000, 2))
    assert _read("rest.server_ms", _run({}, after)) == pytest.approx(0.5)
    assert _read("rest.server_ms", _run({}, _counters(
        rest_search=(12_000_000, 2)))) is None


def test_xla_compiles_are_the_compiles_less_the_persistent_cache_hits():
    run = _run({"es.jit.compiles": 85.0, "es.jit.persistent_cache_hits": 7.0},
               {"es.jit.compiles": 85.0})
    assert _read("device_programs.xla_compiles", run) == 78.0
    cold = _run({"es.jit.compiles": 85.0, "es.jit.persistent_cache_hits": 0},
                {})
    assert _read("device_programs.xla_compiles", cold) == 85.0
    # the parent counts compiles and no hits: nothing to read
    assert _read("device_programs.xla_compiles",
                 _run({"es.jit.compiles": 85.0}, {})) is None
    assert _read("device_programs.xla_compiles", _run({}, {})) is None


def test_plan_shapes_are_the_misses_counted_when_the_window_starts():
    run = _run({"es.jit.cache.search_solo.misses": 84.0,
                "es.jit.cache.search_solo.hits": 108.0},
               {"es.jit.cache.search_solo.misses": 84.0})
    assert _read("device_programs.plan_shapes", run) == 84.0
    assert _read("device_programs.plan_shapes", _run({}, {})) is None


# -- scopes and programs of a capture ----------------------------------------

@pytest.mark.parametrize("path, want", [
    ("jit(search_solo)/jit(main)/vmap(topk)/jit(_scan_topk_pallas)/pallas_call",
     "topk"),
    ("jit(search_solo)/topk/top_k", "topk"),
    ("jit(search_solo)/vmap(score)/jit(_where)/select_n", "score"),
    ("jit(search_solo)/vmap(vmap(score))/topk/add", "score"),  # the outermost
    ("jit(search_solo)/aggs/add", None),        # scoped in the program, unread
    ("jit(search_solo)/convert_element_type", None),
    ("jit(top_k_with_total)/mul", None),
    ("", None),
])
def test_the_scope_of_an_op_name(path, want):
    assert spans.scope_of(path) == want


def test_program_names_lose_their_fingerprint():
    assert spans.program_of("jit_search_solo(10582219930269109804)") == \
        "jit_search_solo"
    assert spans.program_of("jit_search_solo") == "jit_search_solo"


CAPTURE = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 3000000000 }
    events { metadata_id: 6 offset_ps: 7000000000 duration_ps: 1000000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000
             stats { metadata_id: 2 double_value: 1.0 } }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 7000000000 duration_ps: 600000000 }
    events { metadata_id: 4 offset_ps: 7600000000 duration_ps: 400000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.A = f32[8] fusion()"
    stats { metadata_id: 3 str_value: "loop fusion" }
    stats { metadata_id: 1 str_value: "jit(search_solo)/vmap(score)/mul:" } } }
  event_metadata { key: 2 value { id: 2 name: "%scan_topk = custom-call()"
    stats { metadata_id: 1 ref_value: 4 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.B = f32[8] fusion()"
    stats { metadata_id: 1 str_value: "jit(dense_tfn)/div:" } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.1 = f32[8] copy()" } }
  event_metadata { key: 5 value { id: 5 name: "jit_search_solo(77)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_dense_tfn(9)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "Time Scale Multiplier" } }
  stat_metadata { key: 3 value { id: 3 name: "hlo_category" } }
  stat_metadata { key: 4 value { id: 4
    name: "jit(search_solo)/vmap(topk)/jit(_scan_topk_pallas)/pallas_call:" } } }
planes { name: "/host:CPU"
  lines { name: "engine" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 2500000000 duration_ps: 1500000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 3000000000 duration_ps: 3500000000 } }
  lines { name: "loop" timestamp_ns: 1000000
    events { metadata_id: 4 offset_ps: 5500000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "engine.fetch" } }
  event_metadata { key: 2 value { id: 2 name: "engine.plan" } }
  event_metadata { key: 3 value { id: 3 name: "$app.py:202 <lambda>" } }
  event_metadata { key: 4 value { id: 4 name: "rest.respond" } } }'''


def _serialized(text: str) -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(text)


def test_device_seconds_by_scope_and_by_program():
    # the scope is a stat of the operation's event metadata, spelt out or
    # as a reference to a stat's name
    got = spans.scopes_of(_serialized(CAPTURE))
    assert got["scopes"] == {"score": pytest.approx(1e-3),
                             "topk": pytest.approx(2e-3)}
    assert got["unscoped_s"] == pytest.approx(1e-3)
    assert got["programs"] == {"jit_search_solo": pytest.approx(3e-3),
                               "jit_dense_tfn": pytest.approx(1e-3)}
    nothing = {"scopes": {}, "programs": {}, "unscoped_s": 0.0}
    assert spans.scopes_of(b"") == nothing
    assert spans.scopes_of(_serialized(
        CAPTURE[CAPTURE.index('planes { name: "/host:CPU"'):])) == nothing


def _run_with_capture(tmp_path, serialized: bytes):
    with open(tmp_path / "vm.xplane.pb", "wb") as f:
        f.write(serialized)
    run = harness.Run()
    run.capture = {"started": {"started": True, "dir": str(tmp_path)}}
    return run


def test_an_idle_gap_is_split_over_the_stages_that_overlap_it(tmp_path):
    # the device idles from 3 ms to 7 ms: fetch holds 3-4, plan 4-6,
    # respond 5.5-6.5 beside it on another thread, nothing 6.5-7; a Python
    # frame is no stage
    run = _run_with_capture(tmp_path, _serialized(CAPTURE))
    assert spans.idle_by_stage(run) == {
        "engine.fetch": pytest.approx(1e-3),
        "engine.plan": pytest.approx(2e-3),
        "rest.respond": pytest.approx(1e-3),
        trace.NO_STAGE: pytest.approx(0.5e-3)}
    # no program is shown enqueued, so no lead is taken out by default
    assert spans.idle_by_stage(run, 0.0) == spans.idle_by_stage(run)
    # the device's clock set back by 1 ms: the gap is 4-8 ms
    assert spans.idle_by_stage(run, 1e-3) == {
        "engine.plan": pytest.approx(2e-3),
        "rest.respond": pytest.approx(1e-3),
        trace.NO_STAGE: pytest.approx(1.5e-3)}


@pytest.mark.parametrize("metric, want", [("device.topk_ms", 1.0),
                                          ("device.score_ms", 0.5)])
def test_a_scope_is_read_per_request_sent_and_answered_inside_the_capture(
        tmp_path, metric, want):
    run = _run_with_capture(tmp_path, _serialized(CAPTURE))
    assert _read(metric, run) is None                 # no request inside
    run.traced = [Request(0, 0.0, 0.1, 200), Request(1, 0.1, 0.2, 200),
                  Request(2, 0.2, 0.3, 503)]
    assert _read(metric, run) == pytest.approx(want)


def test_a_run_without_a_capture_reads_no_scope():
    run = harness.Run()
    run.traced = [Request(0, 0.0, 0.1, 200)]
    assert _read("device.topk_ms", run) is None
    run.capture = {"started": {"dir": os.path.join(HERE, "no-such-capture")}}
    assert _read("device.score_ms", run) is None
    assert spans.idle_by_stage(run) == {}


# -- the capture recorded on the chip ----------------------------------------

RECORDED = os.path.join(HERE, "recorded_scopes_capture.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_scopes_capture.expected.json")) as f:
        return trace.load(RECORDED), json.load(f)


def test_scopes_and_programs_of_the_recorded_capture(recorded):
    with open(RECORDED, "rb") as f:
        got = spans.scopes_of(f.read())
    want = recorded[1]
    assert set(got["programs"]) == set(want["programs"]) == {"jit_search_solo"}
    for kind in ("scopes", "programs"):
        assert got[kind] == {k: pytest.approx(v, rel=1e-9)
                             for k, v in want[kind].items()}, kind
    assert got["unscoped_s"] == pytest.approx(want["unscoped_s"], rel=1e-9)
    # the streamed Pallas scan is counted under its scope, not by its name
    assert got["scopes"]["topk"] > 10 * got["scopes"]["score"]


def test_the_recorded_capture_names_its_gaps_by_stage(recorded, tmp_path):
    profile, want = recorded
    reduced = trace.reduce(profile)
    assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-4)
    # what the driver gets: the gaps on the host's clock, split over the stages
    assert reduced["device_lead_s"] == pytest.approx(want["device_lead_s"],
                                                     abs=1e-6)
    assert dict(reduced["idle_gaps"]) == {
        k: pytest.approx(v, rel=1e-4)
        for k, v in want["idle_split_on_host_clock"].items()}
    stages = [name for name, _ in reduced["idle_gaps"] if name != trace.NO_STAGE]
    assert stages[0] == want["largest_gap"] == "engine.dispatch"
    assert not [n for n in stages if ".py:" in n or "$" in n]
    # the expected splits are from a timeline of 1 ns cells
    with open(RECORDED, "rb") as f:
        run = _run_with_capture(tmp_path, f.read())
    got = spans.idle_by_stage(run, 0.0)              # as the capture stands
    assert got == {k: pytest.approx(v, rel=1e-4)
                   for k, v in want["idle_split"].items()}
    assert sum(got.values()) == pytest.approx(
        want["span_s"] - want["busy_s"], rel=1e-4)
    assert spans.idle_by_stage(run) == dict(reduced["idle_gaps"])


def test_the_device_clock_leads_the_host_clock_in_the_recorded_capture(
        recorded, tmp_path):
    """A program starts on the device plane before the host has enqueued it:
    the two clocks of a v5e capture differ. `trace.device_lead` estimates the
    lead as PR 26 measured it by hand (the expected file's `device_lead_s`,
    over the cut's four programs), and the idle split moves from the fetch to
    the dispatch once it is taken out, which is the default."""
    profile, want = recorded
    assert trace.device_lead(profile) == pytest.approx(want["device_lead_s"],
                                                       abs=1e-6)
    with open(RECORDED, "rb") as f:
        run = _run_with_capture(tmp_path, f.read())
    raw = spans.idle_by_stage(run, 0.0)
    set_back = spans.idle_by_stage(run)
    assert set_back == spans.idle_by_stage(run, want["device_lead_s"])
    assert sum(set_back.values()) == pytest.approx(sum(raw.values()))
    assert raw["engine.fetch"] > raw["engine.dispatch"]
    assert set_back["engine.dispatch"] > set_back["engine.fetch"]
    # idle time under the dispatch, a program, is the dispatch itself: the
    # device waits through all of it (1.70 ms, the annotations' mean, less
    # what the launch's own latency hides from the estimate)
    dispatches = [e.duration_ns * 1e-9 for plane in profile.planes
                  for ln in plane.lines for e in ln.events
                  if e.name == "engine.dispatch"]
    assert len(dispatches) == 4
    assert set_back["engine.dispatch"] / 4 == pytest.approx(
        sum(dispatches) / 4, abs=0.2e-3)


LEAD = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 500000000 }
    events { metadata_id: 1 offset_ps: 5000000000 duration_ps: 500000000 }
    events { metadata_id: 1 offset_ps: 9000000000 duration_ps: 500000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 500000000 }
    events { metadata_id: 2 offset_ps: 5000000000 duration_ps: 500000000 }
    events { metadata_id: 2 offset_ps: 9000000000 duration_ps: 500000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_search_solo(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion = f32[8] fusion()" } } }
planes { name: "/host:CPU"
  lines { name: "launcher" timestamp_ns: 0
    ENQUEUED }
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 5200000000 duration_ps: 600000000 } }
  event_metadata { key: 1 value { id: 1 name: "DoEnqueueProgram" } }
  event_metadata { key: 2 value { id: 2 name: "engine.dispatch" } } }'''


def _enqueued_at(*ms):
    from jax.profiler import ProfileData

    events = " ".join(f"events {{ metadata_id: 1 offset_ps: {int(t * 1e9)} "
                      "duration_ps: 20000000 }" for t in ms)
    return ProfileData.from_text_proto(LEAD.replace("ENQUEUED", events))


@pytest.mark.parametrize("enqueued_ms, want_ms", [
    ((1.8, 5.8, 9.8), 0.8),          # every program shown enqueued 0.8 ms late
    ((1.7, 5.8, 9.9), 0.8),          # the median of the readings
    ((1.7, 5.9), 0.9),               # of two, the upper: each is a lower bound
    ((5.8, 9.8), 0.8),               # the first program was enqueued before the capture
    ((0.9, 4.9, 8.9), 0.0),          # the host's clock is not behind: never below 0
    ((), 0.0),                       # no such event: the capture as it stands
])
def test_the_lead_of_a_hand_written_capture(enqueued_ms, want_ms):
    profile = _enqueued_at(*enqueued_ms)
    assert trace.device_lead(profile) == pytest.approx(want_ms * 1e-3, abs=1e-9)
    reduced = trace.reduce(profile)
    assert reduced["device_lead_s"] == pytest.approx(want_ms * 1e-3, abs=1e-9)
    # the device idles 1.5-5 and 5.5-9 ms on its own clock; the dispatch's
    # annotation runs 5.2-5.8 ms on the host's
    under = dict(reduced["idle_gaps"]).get("engine.dispatch", 0.0)
    assert under == pytest.approx(
        {0.0: 0.3e-3, 0.8: 0.6e-3, 0.9: 0.6e-3}[want_ms], abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(1.5e-3)
    assert reduced["span_s"] == pytest.approx(8.5e-3)
