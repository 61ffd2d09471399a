"""The adaptive execution planner (PR 18, ROADMAP item 4).

Covers the tentpole contracts: cold-start fallback byte-identical to
the static priority routing, decision determinism under fixed EMA
state, repricing parity with the PR-14 degradation pins (env vars
untouched), knob bounds (nprobe / wave close / cache admission), the
residual feedback gauges, the decision-latency budget, and the lint
that every arm dispatch site routes through the ARM_SITES registry
(no orphan env-gate routing)."""

import os
import re
from pathlib import Path

import pytest

from elasticsearch_tpu.monitoring.costmodel import KERNEL_COSTS
from elasticsearch_tpu.planner import (
    ARM_SITES, execution_planner, reset_for_tests)

SRC = Path(__file__).resolve().parents[1] / "elasticsearch_tpu"

# one batched-site candidate list (static priority order, exact last)
CANDS = [
    ("fused", "fused.pallas_scan",
     {"queries": 8, "k": 8, "v": 4, "num_docs": 4096}),
    ("impact", "sparse.impact_sum",
     {"queries": 8, "k": 8, "num_docs": 4096, "rows": 2048}),
    ("exact", "batched.disjunction",
     {"queries": 8, "k": 8, "num_docs": 4096, "rows": 2048}),
]


@pytest.fixture(autouse=True)
def _fresh_planner():
    reset_for_tests()
    yield
    reset_for_tests()


def _warm(pl, eff_by_kernel):
    """Seed each kernel's efficiency EMA with one crafted observation."""
    for (arm, kernel, fields) in CANDS:
        eff = eff_by_kernel.get(kernel)
        if eff is not None:
            pl.observe(kernel, fields, 1e-3, {"mfu": eff})


# ---------------------------------------------------------------------------
# cold start = static priority, warm = model argmin, both deterministic
# ---------------------------------------------------------------------------

def test_cold_start_falls_back_to_static_priority():
    pl = execution_planner()
    assert pl.stats()["kernels"] == {}  # genuinely cold
    for _ in range(5):
        assert pl.choose_arm("batched.msearch", CANDS) == "fused"
    st = pl.stats()
    assert st["decisions"] == {"fused": 5}
    assert st["decision_modes"]["static"] == 5
    assert st["decision_modes"]["model"] == 0


def test_partially_cold_state_is_still_static():
    # ONE kernel warm is not enough: any unpredictable survivor keeps
    # the decision on the static fallback (never a partial argmin)
    pl = execution_planner()
    _warm(pl, {"sparse.impact_sum": 0.9})
    assert pl.choose_arm("batched.msearch", CANDS) == "fused"
    assert pl.stats()["decision_modes"]["model"] == 0


def test_disabled_planner_matches_cold_routing():
    pl = execution_planner()
    _warm(pl, {"fused.pallas_scan": 0.01, "sparse.impact_sum": 0.9,
               "batched.disjunction": 0.9})
    pl.configure(enabled=False)
    # warm EMAs, but disabled: identical to the static priority
    assert pl.choose_arm("batched.msearch", CANDS) == "fused"
    assert pl.stats()["decision_modes"]["model"] == 0


def test_env_kill_switch(monkeypatch):
    pl = execution_planner()
    _warm(pl, {"fused.pallas_scan": 0.01, "sparse.impact_sum": 0.9,
               "batched.disjunction": 0.9})
    monkeypatch.setenv("ES_TPU_PLANNER", "0")
    assert not pl.enabled
    assert pl.choose_arm("batched.msearch", CANDS) == "fused"


def test_a_site_of_program_families_keeps_the_static_order_when_warm():
    """PR 35: `model=False` (the merged msearch route, whose arms are
    families of compiled programs): warm EMAs that would send the model to
    the impact arm leave the first candidate chosen; repricing still
    filters."""
    pl = execution_planner()
    _warm(pl, {"fused.pallas_scan": 0.001, "sparse.impact_sum": 0.9,
               "batched.disjunction": 0.2})
    assert pl.choose_arm("batched.msearch", CANDS) == "impact"
    for _ in range(5):
        assert pl.choose_arm("batched.msearch", CANDS, model=False) == "fused"
    assert pl.stats()["decision_modes"]["static"] == 5
    with pl.reprice(["fused"], reason="oom"):
        assert pl.choose_arm("batched.msearch", CANDS,
                             model=False) == "impact"


def test_warm_model_picks_argmin_deterministically():
    pl = execution_planner()
    # fused priced terribly, impact excellent, exact mediocre
    _warm(pl, {"fused.pallas_scan": 0.001, "sparse.impact_sum": 0.9,
               "batched.disjunction": 0.2})
    choices = {pl.choose_arm("batched.msearch", CANDS) for _ in range(50)}
    assert choices == {"impact"}  # fixed EMA state -> one fixed answer
    st = pl.stats()
    assert st["decisions"]["impact"] == 50
    assert st["decision_modes"]["model"] == 50


def test_observe_wall_warms_model_from_wave_attribution():
    """The serving-path feed (flight-recorder decision attribution ->
    observe_wall) must warm the same EMAs the solo paths warm through
    time_kernel: wall-only observations make the model routable."""
    pl = execution_planner()
    for _, kernel, fields in CANDS:
        assert pl.predict_ms(kernel, fields) is None
        # a slow wall -> low recovered efficiency, but WARM
        pl.observe_wall(kernel, fields, 5e-3)
        assert pl.predict_ms(kernel, fields) is not None
    assert pl.choose_arm("batched.msearch", CANDS) in {
        "fused", "impact", "exact"}
    assert pl.stats()["decision_modes"]["model"] == 1
    # non-positive walls and cost-model-less kernels are ignored
    pl.observe_wall("batched.disjunction", CANDS[2][2], 0.0)
    pl.observe_wall("fused.msearch", {"queries": 1}, 1e-3)
    assert "fused.msearch" not in pl.stats()["kernels"]


def test_predict_ms_none_while_cold():
    pl = execution_planner()
    assert pl.predict_ms("fused.pallas_scan", CANDS[0][2]) is None
    _warm(pl, {"fused.pallas_scan": 0.5})
    assert pl.predict_ms("fused.pallas_scan", CANDS[0][2]) > 0


# ---------------------------------------------------------------------------
# repricing: parity with the PR-14 pin behavior, env never touched
# ---------------------------------------------------------------------------

def test_scoped_reprice_filters_candidates_and_lifts():
    pl = execution_planner()
    env_before = os.environ.get("ES_TPU_FUSED")
    with pl.reprice(("fused",), reason="test"):
        assert pl.choose_arm("batched.msearch", CANDS) == "impact"
        assert pl.repriced_arms() == ["fused"]
        with pl.reprice(("impact",)):
            assert pl.choose_arm("batched.msearch", CANDS) == "exact"
            assert pl.stats()["decision_modes"]["repriced"] >= 1
    assert pl.repriced_arms() == []
    assert pl.choose_arm("batched.msearch", CANDS) == "fused"
    assert os.environ.get("ES_TPU_FUSED") == env_before


def test_all_arms_repriced_falls_back_to_exact():
    # the PR-14 stage-3 contract: the last candidate is the always-
    # correct smallest-footprint arm, served even when "repriced"
    pl = execution_planner()
    with pl.reprice(("fused", "impact", "exact")):
        assert pl.choose_arm("batched.msearch", CANDS) == "exact"
        assert pl.stats()["decision_modes"]["repriced"] == 1


def test_standing_repricer_follows_predicate():
    pl = execution_planner()
    state = {"degraded": True}
    pl.add_repricer("fused", "t", lambda: state["degraded"])
    assert pl.choose_arm("batched.msearch", CANDS) == "impact"
    state["degraded"] = False  # ramp recovered: no un-registration needed
    assert pl.choose_arm("batched.msearch", CANDS) == "fused"
    pl.remove_repricer("fused", "t")


# ---------------------------------------------------------------------------
# knob bounds
# ---------------------------------------------------------------------------

ANN_FIELDS = {"queries": 1, "dims": 16, "tile": 64, "nprobe": 8}


def test_advise_nprobe_cold_or_untargeted_is_identity():
    pl = execution_planner()
    assert pl.advise_nprobe(7, 32, ANN_FIELDS) == 7  # no target set
    pl.configure(knn_target_ms=5.0)
    assert pl.advise_nprobe(7, 32, ANN_FIELDS) == 7  # cold EMA


def test_advise_nprobe_bounds():
    pl = execution_planner()
    pl.observe("ann.gather_scan", ANN_FIELDS, 1e-3, {"mfu": 0.5})
    pl.configure(knn_target_ms=60_000.0)  # huge budget -> full coverage
    assert pl.advise_nprobe(7, 32, ANN_FIELDS) == 32
    pl.configure(knn_target_ms=1e-9)      # impossible budget -> floor 1
    assert pl.advise_nprobe(7, 32, ANN_FIELDS) == 1
    assert pl.stats()["knobs"]["nprobe_adjustments"] >= 2


def test_advise_wave_close_bounds():
    pl = execution_planner()
    # cold (no drain / arrival EMAs): configured values untouched
    assert pl.advise_wave_close(256, 0.002, 3, None, None) == (256, 0.002)
    assert pl.advise_wave_close(256, 0.002, 3, 5.0, None) == (256, 0.002)
    # warm: clamped to [1, max_wave] x [0, max_wait_s]
    for depth, drain, rate in ((0, 1.0, 10.0), (3, 5.0, 1000.0),
                               (300, 50.0, 1e6), (1, 1e-3, 1e-3)):
        w, t = pl.advise_wave_close(256, 0.002, depth, drain, rate)
        assert 1 <= w <= 256, (depth, drain, rate, w)
        assert 0.0 <= t <= 0.002, (depth, drain, rate, t)
    # disabled: identity even when warm
    pl.configure(enabled=False)
    assert pl.advise_wave_close(256, 0.002, 3, 5.0, 10.0) == (256, 0.002)


def test_cache_admission_floor():
    pl = execution_planner()
    assert pl.admit_cache(0.0001)   # floor 0 admits everything
    assert pl.admit_cache(None)
    pl.configure(cache_min_recompute_us=100.0)
    assert not pl.admit_cache(0.05)  # 50 us recompute: not worth caching
    assert pl.admit_cache(1.0)       # 1 ms recompute: cache it
    assert pl.admit_cache(None)      # unknown cost always admits
    knobs = pl.stats()["knobs"]
    assert knobs["cache_rejections"] == 1
    assert knobs["cache_admissions"] == 1


# ---------------------------------------------------------------------------
# residual feedback + decision latency
# ---------------------------------------------------------------------------

def test_residual_exported_as_gauge_and_histogram():
    from elasticsearch_tpu.telemetry import metrics

    pl = execution_planner()
    fields = CANDS[2][2]
    pl.observe("batched.disjunction", fields, 1e-3, {"mfu": 0.5})
    # second observation: the pre-update EMA predicts, residual lands
    pl.observe("batched.disjunction", fields, 2e-3, {"mfu": 0.25})
    st = pl.stats()["kernels"]["batched.disjunction"]
    assert st["predictions"] >= 1
    assert st["residual_abs_ema"] > 0
    snap = metrics.snapshot()
    assert "es.planner.residual.batched.disjunction" in snap["gauges"]
    assert snap["histograms"]["es.planner.residual"]["count"] >= 1
    worst, worst_val = pl.worst_kernel()
    assert worst == "batched.disjunction" and worst_val > 0


def test_decision_latency_under_budget():
    from elasticsearch_tpu.telemetry import metrics

    pl = execution_planner()
    _warm(pl, {"fused.pallas_scan": 0.5, "sparse.impact_sum": 0.5,
               "batched.disjunction": 0.5})
    for _ in range(100):
        pl.choose_arm("batched.msearch", CANDS)
    h = metrics.snapshot()["histograms"]["es.planner.decision_us"]
    assert h["count"] >= 100
    assert h["p50"] < 100.0, f"median decision latency {h['p50']} us"


# ---------------------------------------------------------------------------
# settings wiring
# ---------------------------------------------------------------------------

def test_engine_settings_drive_planner_config(tmp_path):
    from elasticsearch_tpu.engine import Engine

    e = Engine(str(tmp_path / "d"))
    pl = execution_planner()
    try:
        assert pl.enabled
        e.settings.update({"transient": {
            "planner.enabled": False, "planner.ema.alpha": 0.5,
            "planner.knn.target_ms": 7.5,
            "planner.cache.min_recompute_us": 25.0}})
        st = pl.stats()
        assert st["enabled"] is False
        assert st["config"] == {"ema_alpha": 0.5, "knn_target_ms": 7.5,
                                "cache_min_recompute_us": 25.0}
        e.settings.update({"transient": {"planner.enabled": True}})
        assert pl.enabled
    finally:
        e.close()


# ---------------------------------------------------------------------------
# lint: every dispatch site routes through the registry
# ---------------------------------------------------------------------------

def _source_texts():
    return {p: p.read_text() for p in SRC.rglob("*.py")}


def test_lint_choose_arm_sites_match_registry():
    sites = set()
    for path, text in _source_texts().items():
        sites.update(re.findall(r'choose_arm\(\s*"([^"]+)"', text))
    assert sites == set(ARM_SITES), (
        f"choose_arm call sites {sites} != ARM_SITES registry "
        f"{set(ARM_SITES)} — register new dispatch sites, remove dead ones")


def test_lint_registry_kernels_are_costed():
    for site, arms in ARM_SITES.items():
        assert list(arms) and "exact" in arms, (site, arms)
        for arm, kernel in arms.items():
            assert kernel in KERNEL_COSTS, (
                f"{site}/{arm} prices through unknown kernel {kernel}")
            assert KERNEL_COSTS[kernel] is not None, (
                f"{site}/{arm} kernel {kernel} has no cost fn — "
                "the planner could never price it")


def test_lint_no_orphan_fused_env_routing():
    """The PR-14 recovery path must route through planner repricing:
    nothing outside the fused-arm *eligibility* gates may WRITE the
    ES_TPU_FUSED env var (reading the gate is fine)."""
    offenders = []
    for path, text in _source_texts().items():
        if re.search(r'os\.environ\[\s*"ES_TPU_FUSED"\s*\]\s*=', text):
            offenders.append(str(path))
    assert not offenders, (
        f"env-pin routing outside the planner: {offenders}")
