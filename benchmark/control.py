#!/usr/bin/env python3
"""The control of `correct`: answers that a lower precision would give, held
against the same comparison and the same limits as a run. It has to come out
as not correct. A builder's aid and a test's helper; no run of the benchmark
calls it.

    python benchmark/control.py --workload passage.solo.c1 --seeds 11 12 13
        the reference in bfloat16 (the nearest precision below the float32 the
        configuration states) put in the program's place, at the cell's own
        size: corpus, pool and sample as a run of that seed draws them

    python benchmark/control.py --workload passage.solo.c1 --seeds 11 \\
            --program-env ES_TPU_IMPACT_DTYPE=int8 --seconds 10
        the program itself with its own lower-precision path switched on (int8
        impact codes for uint16): a whole run on the chip
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import run as harness  # noqa: E402
from benchlib import compare as cmp  # noqa: E402
from benchlib.reference import Reference  # noqa: E402
from benchlib.stats import Request  # noqa: E402


def answers_of(ref: Reference, pool, queries, k: int) -> list[Request]:
    """What a server that scored as `ref` does would answer to `queries`
    (indices into the pool): one Request each, as the window records them."""
    ref.prepare({t for q in queries for t in pool[q]})
    out = []
    for i, q in enumerate(queries):
        ids, scores, total = ref.top(pool[q], k)
        out.append(Request(q, i * 1e-3, i * 1e-3 + 5e-4, 200, 0.0, ids, scores,
                           {"value": total, "relation": "eq"}))
    return out


def reference_control(spec: dict, seed: int, precision: str = "bf16") -> dict:
    config, traffic = spec["config"], spec["traffic"]
    corpus, pool, _ = harness.make_inputs(config, traffic, seed)
    k = int(config["search"]["size"])
    shards = int(config["number_of_shards"])
    sample = cmp.draw_sample(seed, list(range(len(pool))), pool,
                             int(traffic["check_sample"]))
    low = Reference(corpus.lens, corpus.tok, shards, precision=precision)
    served = answers_of(low, pool, sample, k)
    ref = Reference(corpus.lens, corpus.tok, shards)
    return cmp.compare(ref, pool, served, sample, k, config["limits"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program-env", action="append", default=[],
                    metavar="NAME=VALUE")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    spec = harness.resolve(harness.ROOT, args.workload)
    failed_to_fail = 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        if args.program_env:
            env = dict(kv.split("=", 1) for kv in args.program_env)
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   server_env=env)
            verdict = {"correct": res["correct"], "numbers": res["compared"]}
        else:
            verdict = reference_control(spec, seed)
        line = {"control": args.program_env or "reference-bf16", "seed": seed,
                "correct": verdict["correct"],
                "numbers": {k: v["value"] for k, v in verdict["numbers"].items()},
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        failed_to_fail += bool(verdict["correct"])
    if failed_to_fail:
        print(f"control: {failed_to_fail} seed(s) came out correct",
              file=sys.stderr)
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
