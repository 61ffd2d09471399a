"""Host cost of the stage entries that a solo search opens beside the stages
its engine thread already had:

    python3 scripts/span_cost.py [--searches 2000] [--repeats 25]

One search's tree of spans, as the REST handler and the engine thread open
it (`rest.search` > `rest.read`, `engine.queue`, `engine.search` >
`engine.route` and `executeQueryPhase` > `engine.parse`, `plan`, `account`,
`dispatch`, `fetch`, `account`, `unpack`, `collect`, `collect`, then
`account` for the slow log; `rest.resume`, `rest.respond`), with no work
inside any span, against the same tree without `rest.read`, `engine.route`,
`engine.account`, `engine.unpack` and `rest.resume`. Both run `--searches`
times a round, `--repeats` rounds of each in turn. Twice: with the
capture's annotations off (any run but a traced one) and on, inside a live
`jax.profiler` trace (a `--trace 1` window's last seconds). Counters are
summed as a search's are. One JSON line: microseconds a search, the best
round of each tree and the median of the rounds' differences, also in
chiprun_out/span_cost.json.
"""
import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from elasticsearch_tpu import telemetry  # noqa: E402
from elasticsearch_tpu.telemetry import (TRACER, account, begin_lead,  # noqa: E402
                                         end_lead)


def search(new: bool) -> None:
    span = TRACER.span
    with span("rest.search"):
        if new:
            with span("rest.read"):
                pass
        t = time.perf_counter_ns()
        TRACER.record("engine.queue", t, t)
        with span("engine.search"):
            if new:
                begin_lead("engine.route")
            with span("executeQueryPhase"):
                for stage in ("engine.parse", "engine.plan"):
                    with span(stage):
                        pass
                if new:
                    with account():
                        pass
                for stage in ("engine.dispatch", "engine.fetch"):
                    with span(stage):
                        pass
                if new:
                    with account():
                        pass
                    with span("engine.unpack"):
                        pass
                for stage in ("engine.collect", "engine.collect"):
                    with span(stage):
                        pass
            if new:
                with account():
                    pass
                end_lead()
        if new:
            t = time.perf_counter_ns()
            TRACER.record("rest.resume", t, t)
        with span("rest.respond"):
            pass


def us_a_search(new: bool, searches: int) -> float:
    t0 = time.perf_counter()
    for _ in range(searches):
        search(new)
    return (time.perf_counter() - t0) / searches * 1e6


def measure(searches: int, repeats: int) -> dict:
    """Rounds of the two trees in turn, so that both meet the same load of
    the host: the best round of each, and the median of the rounds'
    differences."""
    old, new = [], []
    for _ in range(repeats):
        old.append(us_a_search(False, searches))
        new.append(us_a_search(True, searches))
    return {"before_us": round(min(old), 3), "after_us": round(min(new), 3),
            "added_us": round(statistics.median(
                b - a for a, b in zip(old, new)), 3)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--searches", type=int, default=2000)
    ap.add_argument("--repeats", type=int, default=25)
    args = ap.parse_args()
    out = {"searches": args.searches, "repeats": args.repeats,
           "annotations_off": measure(args.searches, args.repeats)}
    import jax

    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        telemetry.annotate_stages(True)
        try:
            out["annotations_on"] = measure(args.searches // 4, args.repeats)
        finally:
            telemetry.annotate_stages(False)
            jax.profiler.stop_trace()
    out["backend"] = jax.default_backend()
    line = json.dumps(out)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "span_cost.json"), "w") as f:
        f.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
