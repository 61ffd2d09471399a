#!/usr/bin/env python3
"""Several runs of run.py in one call, one after another, each a process of
its own. A builder's aid for chip calls; the driver never runs it.

    python benchmark/loop.py --tag A --seconds 20 passage.solo.c1:11:0 passage.solo.c1:12:1

Each run is `<workload>:<seed>:<trace>`. Every last line goes to
chiprun_out/<tag>.jsonl with the run's wall seconds and exit code, and the
end of each run's standard error to chiprun_out/<tag>.err. At the end, for
each cell with three plain runs or more in the call: every end-to-end metric's
median and its spread over them, as `benchlib/stats.spread` has it (README.md,
Bounds).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchlib.stats import spread  # noqa: E402


def summary(records: list[dict]) -> list[str]:
    """One line a cell and end-to-end metric over the plain runs of
    `records` (loop.py's own), cells in their order of first appearance."""
    sets: dict = {}
    for rec in records:
        workload, _, traced = rec["run"].split(":")
        if traced == "0" and rec["result"].get("metrics"):
            for name, m in rec["result"]["metrics"].items():
                sets.setdefault((workload, name), []).append(m["value"])
    return [f"{workload} {name}: n={len(v)} median={statistics.median(v):.6g} "
            f"spread={spread(v):.4f} trimmed={spread(v, trimmed=True):.4f}"
            for (workload, name), v in sets.items() if len(v) >= 3]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--budget", type=float, default=None,
                    help="start no run after this many seconds")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    t_all = time.perf_counter()
    bad = 0
    records = []
    for spec in args.runs:
        if args.budget and time.perf_counter() - t_all > args.budget:
            print(f"loop: budget spent, {spec} not started", flush=True)
            continue
        workload, seed, traced = spec.split(":")
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               workload, "--seed", seed, "--seconds", str(args.seconds),
               "--trace", traced]
        t0 = time.perf_counter()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        last = p.stdout.strip().splitlines()[-1:] or [""]
        try:
            res = json.loads(last[0])
        except ValueError:
            res = {}
        rec = {"run": spec, "rc": p.returncode, "wall_s": wall, "result": res}
        records.append(rec)
        with open(os.path.join(out_dir, f"{args.tag}.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        with open(os.path.join(out_dir, f"{args.tag}.err"), "a") as f:
            f.write(f"=== {spec} rc={p.returncode} wall={wall:.1f}\n"
                    + p.stderr[-6000:] + "\n")
        bad += p.returncode != 0 or not res.get("correct")
        m = {k: round(v["value"], 4) for k, v in res.get("metrics", {}).items()}
        c = {k: v["value"] for k, v in res.get("compared", {}).items()}
        w = res.get("window", {})
        print(f"{spec} rc={p.returncode} wall={wall:.0f}s correct="
              f"{res.get('correct')} {json.dumps(m)} compared={json.dumps(c)} "
              f"compiles_in_window={w.get('compiles_in_window')} "
              f"cache_hits={w.get('request_cache_hits')} "
              f"setup={json.dumps(w.get('setup'))}", flush=True)
        if p.returncode != 0:
            print(p.stderr[-3000:], flush=True)
    for line in summary(records):
        print(line, flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
