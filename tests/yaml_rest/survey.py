"""Survey tool: run reference YAML suites against the app and report
pass/fail/skip per test. Used to curate tests/test_yaml_rest.py's manifest.

    JAX_PLATFORMS=cpu python -m tests.yaml_rest.survey search index ...
"""

from __future__ import annotations

import asyncio
import os
import sys
import traceback

# the survey is a CPU tool (same platform pin as tests/conftest.py): it
# must never take the chip from a process that serves or measures on it
os.environ["JAX_PLATFORMS"] = "cpu"

from aiohttp.test_utils import TestClient, TestServer  # noqa: E402

from elasticsearch_tpu.rest import make_app

from . import SUITES, SkipTest, StepFailure, YamlRunner, load_suite


def run_one(rel: str, name: str, setup, steps, verbose=False):
    loop = asyncio.new_event_loop()

    async def make():
        app = make_app()
        client = TestClient(TestServer(app))
        await client.start_server()
        return client

    client = loop.run_until_complete(make())
    try:
        r = YamlRunner(client, loop.run_until_complete)
        r.steps(setup)
        r.steps(steps)
        return "pass", ""
    except SkipTest as e:
        return "skip", str(e)
    except Exception as e:
        if verbose:
            traceback.print_exc()
        return "fail", f"{type(e).__name__}: {str(e)[:160]}"
    finally:
        loop.run_until_complete(client.close())
        loop.close()


def main():
    dirs = sys.argv[1:] or ["search"]
    verbose = False
    totals = {"pass": 0, "fail": 0, "skip": 0}
    for d in dirs:
        base = SUITES / d
        files = sorted(base.glob("*.yml")) if base.is_dir() else [SUITES / d]
        for f in files:
            rel = str(f.relative_to(SUITES))
            try:
                setup, _td, tests = load_suite(rel)
            except Exception as e:
                print(f"LOADFAIL {rel}: {e}")
                continue
            for name, steps in tests:
                st, why = run_one(rel, name, setup, steps, verbose)
                totals[st] += 1
                mark = {"pass": "PASS", "fail": "FAIL", "skip": "skip"}[st]
                print(f"{mark} {rel} :: {name}" + (f"  [{why}]" if why else ""))
    print(totals)


if __name__ == "__main__":
    main()
