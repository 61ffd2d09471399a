"""Multichip dry run + pjit parity gate (PR 10, CI satellite).

Runs `__graft_entry__.dryrun_multichip` — the production sharded stack
(bool/aggs/knn + batched msearch) on a device mesh with parity
asserted against single-device AND the shard_map fallback — and exits
nonzero on any divergence.

A CPU tool: the child process gets `JAX_PLATFORMS=cpu` and 8 virtual CPU
devices outright (a chip belongs to one process at a time; the four-chip
path on hardware is `chip_smoke.py --chips 4`). The virtual mesh is a
lowering approximation of the target platform, but parity is parity: a
divergence exits 1.

Optionally writes the MULTICHIP_rNN.json record shape with --record.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    record_path = None
    args = sys.argv[1:]
    if "--record" in args:
        record_path = args[args.index("--record") + 1]

    n = 8
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}"
                        ).strip()

    out = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n})"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    ok = out.returncode == 0
    tail = (out.stdout.strip().splitlines() or [""])[-1]
    print(tail)
    if not ok:
        err_tail = "\n".join(out.stderr.strip().splitlines()[-8:])
        print(f"[multichip-dryrun] FAILED (virtual CPU mesh):\n{err_tail}",
              file=sys.stderr)
    else:
        print(f"[multichip-dryrun] OK (virtual CPU mesh, {n} devices)")
    if record_path:
        rec = {"n_devices": n, "rc": out.returncode, "ok": ok,
               "skipped": False, "enforcing": True,
               "tail": out.stdout}
        if not ok:
            rec["stderr_tail"] = out.stderr[-2000:]
        with open(record_path, "w") as f:
            json.dump(rec, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
