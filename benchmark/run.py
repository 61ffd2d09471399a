#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness never touches JAX while it measures: it starts
`python -m elasticsearch_tpu.rest.server` as its one child (the child holds
the chip), drives it over HTTP and stops it before it prints its last line.

One run, in this order: corpus and query pool from --seed -> start server ->
cluster settings of the configuration -> create index, _bulk, _refresh -> on
several chips, see that the pack lies on all of them (`pack_spread`) ->
warm-up (whole pool through the cell's own clients, until one full pass
compiles nothing) -> window of --seconds -> read counters -> stop server ->
compare the window's own answers with the NumPy reference -> last line.
`setup_s` is process start to the first request of the window.

A cell is data: `BENCHMARK.json` names its configuration and traffic mix, and
`benchmark/configs/<config>.json`, `benchmark/traffic/<traffic>.json` and
`benchmark/layer_metrics/<metric>.py` are found by those names; the
configuration's `kind` names `benchmark/kinds/<kind>.py`, which makes the
corpus, the pool, the bodies and the index, keeps what an answer says and
judges the answers (README.md). Nothing here knows one kind from another.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchlib import stats, trace  # noqa: E402
from benchlib.client import LoadGenerator, load  # noqa: E402
from benchlib.server import Server  # noqa: E402

INDEX = "c1"
CAPTURE_S = 3.0  # the traced part of a --trace 1 window, at its end
DEFAULT_KIND = "bm25_match"  # a configuration that names none
# what a kind module provides (README.md, "A kind of deployment")
KIND_SEAMS = ("corpus", "pool", "body", "index_body", "bulk", "digest",
              "judge")


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T_PROCESS:7.1f}] {msg}", file=sys.stderr,
          flush=True)


class BenchError(RuntimeError):
    """The run cannot give a result: no metrics are printed."""


# ---------------------------------------------------------------------------
# resolving a cell to its files, by name
# ---------------------------------------------------------------------------

def read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchError(f"cannot read {path}: {e}") from None


def resolve(spec_root: str, workload: str) -> dict:
    """-> {"cell", "config", "traffic", "kind", "bench_dir", "end_to_end",
    "per_layer"} for one cell of <spec_root>/BENCHMARK.json."""
    bench = read_json(os.path.join(spec_root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    entry = next((c for c in bench["configs"] if c["name"] == cell["config"]),
                 None)
    if entry is None:
        raise BenchError(f"workload {workload!r} names configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")
    config = read_json(os.path.join(spec_root, entry["file"]))
    bench_dir = os.path.dirname(os.path.dirname(entry["file"]))
    traffic = read_json(os.path.join(
        spec_root, bench_dir, "traffic", cell["traffic"] + ".json"))
    if int(config["chips"]) != int(cell["chips"]):
        raise BenchError(f"cell {workload!r} asks for {cell['chips']} chips, "
                         f"its configuration for {config['chips']}")

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    bench_dir = os.path.join(spec_root, bench_dir)
    return {"cell": cell, "config": config, "traffic": traffic,
            "kind": kind_module(bench_dir, config.get("kind", DEFAULT_KIND)),
            "bench_dir": bench_dir,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def load_module(path: str, prefix: str, name: str):
    """The module at `path`, under the name `prefix` + `name` in
    `sys.modules` (where a dataclass of it looks itself up)."""
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def layer_reader(bench_dir: str, name: str):
    """The `read(run)` function of benchmark/layer_metrics/<name>.py."""
    path = os.path.join(bench_dir, "layer_metrics", name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"per-layer metric {name!r} has no reader at {path}")
    return load_module(path, "layer_metric_", name).read


def kind_module(bench_dir: str, name: str):
    """The module benchmark/kinds/<name>.py, which provides every one of
    KIND_SEAMS."""
    path = os.path.join(bench_dir, "kinds", name + ".py")
    if not os.path.isfile(path):
        there = sorted(f[:-3] for f in os.listdir(os.path.dirname(path))
                       if f.endswith(".py"))
        raise BenchError(f"no kind {name!r}: benchmark/kinds/ has {there}")
    mod = load_module(path, "kind_", name)
    lacks = [s for s in KIND_SEAMS if not callable(getattr(mod, s, None))]
    if lacks:
        raise BenchError(f"kind {name!r} ({path}) lacks {lacks}")
    return mod


def device_of(node: dict, peaks: dict, chips: int, require_chip: bool) -> dict:
    """The device as the server reports it; on anything but `chips` TPU chips
    of a kind peaks.json knows, an error."""
    mem = node["device"]["memory"]
    device = {"platform": mem.get("backend"), "kind": mem.get("device_kind"),
              "count": mem.get("device_count")}
    if require_chip:
        if device["platform"] != "tpu":
            raise BenchError(f"the server runs on platform "
                             f"{device['platform']!r}, not on a TPU")
        if device["kind"] not in peaks:
            raise BenchError(f"device kind {device['kind']!r} is not in "
                             f"peaks.json ({sorted(peaks)})")
        if device["count"] != chips:
            raise BenchError(f"the server sees {device['count']} chips, the "
                             f"cell asks for {chips}")
    return device


def memory_peaks(node: dict) -> list[int]:
    """Peak bytes in use on each device the server reports, in its order."""
    return [int(d.get("peak_bytes_in_use", 0))
            for d in node["device"]["memory"].get("devices", [])]


def memory_peak(node: dict) -> int:
    """Peak bytes in use on the fullest device."""
    return max(memory_peaks(node)
               + [int(node["device"]["memory"].get("peak_bytes_in_use", 0))])


def pack_spread(node: dict, chips: int, require_chip: bool) -> list[int]:
    """Bytes each device holds once the index is loaded (the allocator's own
    `bytes_in_use` where the backend has one, else the live arrays'), for a
    cell on several chips. An error unless the `chips` fullest devices each
    hold at least an eighth of all bytes held (chip_smoke.py's rule): the
    program falls back to one device in silence where it finds no mesh, and
    a pack on one chip of four is another deployment. On the chips there are
    exactly `chips` devices; off them (`require_chip` false) the server may
    report more, and the rule is the same."""
    held = [int(d.get("bytes_in_use", d.get("live_bytes", 0)))
            for d in node["device"]["memory"].get("devices", [])]
    fullest = sorted(held, reverse=True)[:chips]
    if (len(held) < chips or (require_chip and len(held) != chips)
            or any(h * 8 < sum(held) for h in fullest)):
        raise BenchError(f"the pack is not spread over {chips} devices: "
                         f"bytes held per device {held}")
    return held


def counters_of(node: dict) -> dict:
    jit = node["device"]["jit"]
    rc = node["indices"]["request_cache"]
    return {"compiles": int(jit["compiles"]),
            "compile_ms": float(jit["compile_time_in_millis"]),
            "request_cache_hits": int(rc.get("hit_count", 0)),
            "counters": node["metrics"]["counters"]}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Run:
    """What one run learned; the per-layer readers take their numbers from
    it (README.md lists the fields)."""

    def __init__(self):
        self.seconds = 0.0
        self.requests: list[stats.Request] = []
        self.untraced: list[stats.Request] = []   # answered before the capture
        self.traced: list[stats.Request] = []     # sent and answered inside it
        self.trace: dict | None = None            # trace.reduce()'s result
        self.capture: dict = {}                   # start_capture()'s record
        self.before: dict = {}                    # counters_of() at window start
        self.after: dict = {}                     # ... and after it
        self.setup: dict = {}                     # load_s, refresh_s, docs
        self.peak: dict = {}                      # peaks.json row of the device
        self.pool: list = []
        self.corpus = None
        self.kind = None                          # the cell's kind module
        self._df = None

    def df(self):
        """[vocab] documents that hold each term, from the generator's own
        arrays: the kind's `df(corpus)`."""
        if self._df is None:
            self._df = self.kind.df(self.corpus)
        return self._df


def start_capture(client, run: Run) -> None:
    """Timer body: ask the server for one profiler capture; it runs over the
    last CAPTURE_S seconds of the window."""
    try:
        out = client.call("POST", "/_profiler/start",
                          {"duration": f"{CAPTURE_S + 4:.0f}s"})
        run.capture = {"started": out, "t0": time.perf_counter()}
    except Exception as e:  # noqa: BLE001 - reported by the caller
        run.capture = {"error": f"{type(e).__name__}: {e}"}


def make_inputs(spec: dict, seed: int):
    """-> (corpus, pool, request bodies) of one seed, by the cell's kind."""
    kind, config = spec["kind"], spec["config"]
    corpus = kind.corpus(seed, config)
    pool = kind.pool(seed, corpus, config, spec["traffic"])
    bodies = [json.dumps(kind.body(q, config)).encode() for q in pool]
    return corpus, pool, bodies


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             spec_root: str = ROOT, program_root: str = ROOT,
             require_chip: bool = True, server_factory=Server,
             server_env: dict | None = None) -> dict:
    """-> the result object of the last line. Raises BenchError where the
    run can give none."""
    if not os.path.isdir(os.path.join(program_root, "elasticsearch_tpu")):
        raise BenchError(f"{program_root}/elasticsearch_tpu is missing: the "
                         "benchmark drives the repo's server and is nothing "
                         "without it")
    spec = resolve(spec_root, workload)
    config, traffic = spec["config"], spec["traffic"]
    peaks = read_json(os.path.join(spec["bench_dir"], "peaks.json"))
    readers = ({m["name"]: layer_reader(spec["bench_dir"], m["name"])
                for m in spec["per_layer"]} if traced else {})
    if traced and seconds < 2 * CAPTURE_S:
        raise BenchError(f"--trace 1 needs --seconds >= {2 * CAPTURE_S:.0f}")

    kind = spec["kind"]
    run = Run()
    run.seconds = float(seconds)
    run.kind = kind
    t0 = time.perf_counter()
    run.corpus, run.pool, bodies = make_inputs(spec, seed)
    say(f"corpus: {run.corpus.n_docs} docs of kind "
        f"{config.get('kind', DEFAULT_KIND)}, pool {len(run.pool)}, seed {seed}, "
        f"{time.perf_counter() - t0:.1f} s")

    work = os.path.join(program_root, ".bench_work", workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(work, "trace"))
    # the compile cache is the run's own and goes with `work`: a run that
    # finds what another compiled sets up sooner, by up to a fifth (PERF.md
    # section 2)
    server = server_factory(program_root, os.path.join(work, "data"),
                            os.path.join(work, "server.log"),
                            os.path.join(work, "jax_cache"), server_env)
    try:
        c = server.start()
        say(f"server: up on port {server.port}")
        node = c.node_stats()
        device = device_of(node, peaks, int(config["chips"]), require_chip)
        run.peak = peaks.get(device["kind"], {})
        settings = dict(config.get("settings", {}))
        if traced:
            settings["xpack.profiling.trace_dir"] = os.path.join(work, "trace")
        if settings:
            c.call("PUT", "/_cluster/settings", {"persistent": settings})
        run.setup = load(c, INDEX, kind.index_body(config), run.corpus.n_docs,
                         kind.bulk(run.corpus, config),
                         int(config["bulk_docs"]), say)
        node = c.node_stats()
        fd = node["breakers"]["fielddata"]
        say(f"breaker: the packs charge {fd['estimated_size_in_bytes']} bytes "
            f"to one device, fielddata limit {fd['limit_size_in_bytes']} bytes")
        if int(config["chips"]) > 1:
            held = pack_spread(node, int(config["chips"]), require_chip)
            say(f"spread: bytes held per device {held}")

        lg = LoadGenerator(server.port, INDEX, bodies, kind.digest,
                           int(traffic["clients"]), traffic.get("rate"))
        last = counters_of(c.node_stats())
        for n_pass in range(1, int(traffic["warmup_max_passes"]) + 1):
            t0 = time.perf_counter()
            warm = lg.one_pass()
            bad = [r for r in warm if not r.ok]
            now = counters_of(c.node_stats())
            say(f"warm-up pass {n_pass}: {len(warm)} requests in "
                f"{time.perf_counter() - t0:.1f} s, {len(bad)} failed, "
                f"{now['compiles'] - last['compiles']} compiles "
                f"({(now['compile_ms'] - last['compile_ms']) / 1e3:.1f} s)")
            if bad:
                raise BenchError(f"warm-up: {len(bad)} of {len(warm)} requests "
                                 f"failed, first status {bad[0].status}")
            quiet = now["compiles"] == last["compiles"]
            last = now
            if quiet:
                break
        run.before = last
        run.setup["compile_s"] = last["compile_ms"] / 1e3
        run.setup["warmup_passes"] = n_pass

        if traced:
            cap_timer = threading.Timer(seconds - CAPTURE_S, start_capture,
                                        args=(c, run))
            cap_timer.start()
        setup_s = time.perf_counter() - T_PROCESS
        run.requests, t_zero = lg.window(seconds)
        t_end = time.perf_counter()
        if traced:
            cap_timer.join()
            cap = run.capture
            if "error" in cap or not cap.get("started", {}).get("started"):
                raise BenchError(f"the profiler capture did not start: {cap}")
            stopped = c.call("POST", "/_profiler/stop")
            a, b = cap["t0"] - t_zero, t_end - t_zero
            run.untraced = [r for r in run.requests if r.done < a - 0.05]
            run.traced = [r for r in run.requests if r.sent >= a and r.done <= b]
            # the profiler slows the host: the answered rate beside it and under it
            rates = {"rate_outside_capture": sum(
                         r.ok for r in run.untraced) / (a - 0.05),
                     "rate_inside_capture": sum(
                         r.ok for r in run.traced) / (b - a)}
            say(f"capture: {stopped.get('bytes')} bytes in {stopped.get('dir')}, "
                f"{len(run.traced)} requests inside {b - a:.2f} s of the "
                f"harness's clock; {json.dumps(rates)}")
        else:
            run.untraced = run.requests
        node = c.node_stats()
        run.after = counters_of(node)
        peak_bytes, peaks_per_device = memory_peak(node), memory_peaks(node)
        if not server.alive():
            raise BenchError("the server died during the window")
    except Exception as e:
        say("--- server log tail ---\n" + server.log_tail())
        if isinstance(e, BenchError):
            raise
        raise BenchError(f"{type(e).__name__}: {e}") from e
    finally:
        server.stop()
    say("server: stopped")

    summary = stats.window_summary(run.requests, seconds)
    say(f"window: {json.dumps(summary)}; compiles in window "
        f"{run.after['compiles'] - run.before['compiles']}, request-cache hits "
        f"{run.after['request_cache_hits'] - run.before['request_cache_hits']}")
    if summary["attempted"] == summary["failed"]:
        raise BenchError("no request of the window was answered")

    # the comparison, once the window has closed and the server is gone
    t0 = time.perf_counter()
    verdict = kind.judge(seed, run.corpus, run.pool, run.requests, config,
                         traffic)
    say(f"reference: {verdict['compared']} answers to {verdict['queries']} "
        f"queries compared in {time.perf_counter() - t0:.1f} s")

    metrics = {}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    result = {"correct": verdict["correct"], "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": metrics,
              "device": dict(device, memory_peak_bytes=peak_bytes,
                             memory_peak_bytes_per_device=peaks_per_device)}
    if traced:
        cap_dir = stopped.get("dir") or os.path.join(work, "trace")
        xplane = trace.find_xplane(cap_dir)
        t0 = time.perf_counter()
        profile = trace.load(xplane) if xplane else None
        run.trace = trace.reduce(profile) if profile else None
        say(f"trace: {xplane} reduced in {time.perf_counter() - t0:.1f} s")
        if run.trace is None and require_chip:
            raise BenchError("the capture holds no operation on a device "
                             f"({xplane})")
        if run.trace is not None:
            result["device"]["busy_s"] = run.trace["busy_s"]
            result["device"]["window_s"] = run.trace["span_s"]
            result["device"]["device_lead_s"] = run.trace["device_lead_s"]
            result["breakdown"] = {"device_ops": run.trace["device_ops"],
                                   "idle_gaps": run.trace["idle_gaps"]}
        for name, read in readers.items():
            value = read(run)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": units[name]}
    else:
        values = {"search_p50_ms": summary.get("p50_ms"),
                  "search_p95_ms": summary.get("p95_ms"),
                  "search_qps": summary["qps"], "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if m["name"] not in values:
                raise BenchError(f"end-to-end metric {m['name']!r} is not one "
                                 f"run.py takes ({sorted(values)})")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["seed"] = seed
    result["workload"] = workload
    result["window"] = dict(
        summary, seconds=seconds, setup=run.setup,
        compiles_in_window=run.after["compiles"] - run.before["compiles"],
        request_cache_hits=(run.after["request_cache_hits"]
                            - run.before["request_cache_hits"]))
    result["window"]["answers_compared"] = verdict["compared"]
    if traced:
        result["window"].update(rates)
    result["compared"] = verdict["numbers"]
    shutil.rmtree(work, ignore_errors=True)
    return result


def main(argv=None, **kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), **kw)
    except BenchError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1
    for name, n in result["compared"].items():
        print(f"compared {name}: {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
