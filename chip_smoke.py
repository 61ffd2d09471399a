#!/usr/bin/env python3
"""Chip smoke: the lexical search path, REST -> BM25 -> compiled kernels,
on one TPU chip at the C1 deployment (bench.py: msmarco-passage-like docs,
Zipf over a 100k vocabulary, Poisson(40) lengths, one shard), cut from 1M
to 524,288 docs: at 1M the 12.06 GB pack is refused by the default
fielddata breaker (40% of one chip's HBM), and a cold-cache run takes
1,009 s of the 1,200 s limit, most of it XLA compiles (PERF.md, PR 22).

This script never initialises a JAX backend — the chip belongs to one
process, and that process is the server it starts:

    python -m elasticsearch_tpu.rest.server --port <free> --data-path <dir>

It drives the server over HTTP (bulk load, refresh, solo `_search`,
`_msearch` through the serving front end, concurrent `_search`), checks
the answers against a plain NumPy BM25 computed here from the
generator's own arrays, checks from `_nodes/stats` that the device is a
TPU and that the compiled kernels ran, and prints as its LAST line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 only if every phase and every check passed. Off the chip
(JAX_PLATFORMS=cpu, small --docs) every phase still runs and the answers
check still decides; the device check then fails the run and no result
line is printed.

`--chips 4` runs only the sharded phase: the same corpus in an index of
four shards on a server that sees four chips, `_msearch` + `_search`,
the same reference check, and a check that every chip holds pack bytes.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import http.client
import importlib.util
import json
import math
import os
import re
import shutil
import socket
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(HERE, "elasticsearch_tpu")
INDEX = "c1"
K1, B = 1.2, 0.75
TOP_K = 10
FAILURES: list[str] = []


def say(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    FAILURES.append(msg)
    say(f"FAIL: {msg}")


def _load(rel: str):
    """Load one table-code module of the repo by path, so that no package
    __init__ (and with it no JAX import) runs in this process."""
    path = os.path.join(PKG, rel)
    spec = importlib.util.spec_from_file_location(
        "_smoke_" + os.path.basename(rel)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# corpus + queries (bench.py build_corpus / sample_queries, seeded)
# ---------------------------------------------------------------------------

def build_corpus(rng, n_docs: int, vocab: int, doc_len: int):
    zipf = 1.0 / np.arange(1, vocab + 1)
    zipf /= zipf.sum()
    lens = rng.poisson(doc_len, size=n_docs).clip(4, None)
    tok = rng.choice(vocab, size=int(lens.sum()), p=zipf)
    return lens, tok


def sample_queries(rng, lens, starts, tok, n_queries: int) -> list[list[int]]:
    """Query terms drawn from real documents, 1 to 4 per query, deduped."""
    out = []
    for d in rng.integers(0, len(lens), size=n_queries):
        n_terms = int(rng.integers(1, 5))
        picks = tok[starts[d] + rng.integers(0, lens[d], size=n_terms)]
        out.append([int(t) for t in dict.fromkeys(picks.tolist())])
    return out


def search_body(terms: list[int]) -> dict:
    return {"query": {"match": {"body": " ".join(f"t{t}" for t in terms)}},
            "size": TOP_K, "_source": False, "track_total_hits": True}


# ---------------------------------------------------------------------------
# the plain reference: NumPy BM25 with Lucene's norm quantisation
# ---------------------------------------------------------------------------

class Reference:
    def __init__(self, lens, tok, num_shards: int):
        sf = _load("index/smallfloat.py")
        self.n = len(lens)
        self.tok = tok
        self.doc_of_tok = np.repeat(np.arange(self.n, dtype=np.int64), lens)
        self.dl = sf.quantize_lengths(lens).astype(np.float64)
        self.avgdl = float(lens.sum()) / self.n
        self.num_shards = num_shards
        self._shard_for_id = _load("cluster/routing.py").shard_for_id
        self._postings: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    def prepare(self, terms) -> None:
        """One pass over the token stream for every term the checked
        queries use: term -> (docs ascending, tf)."""
        want = np.unique(np.asarray(sorted(terms), np.int64))
        sel = np.isin(self.tok, want)
        key = self.tok[sel].astype(np.int64) * self.n + self.doc_of_tok[sel]
        uniq, tf = np.unique(key, return_counts=True)
        t_of, d_of = uniq // self.n, uniq % self.n
        bounds = np.searchsorted(t_of, np.append(want, want[-1] + 1))
        for i, t in enumerate(want.tolist()):
            lo, hi = bounds[i], bounds[i + 1]
            self._postings[t] = (d_of[lo:hi], tf[lo:hi].astype(np.float64))

    def top(self, terms: list[int]):
        """-> (top-10 doc indices in rank order, exact total hits)."""
        scores = np.zeros(self.n, np.float64)
        for t in terms:
            docs, tf = self._postings[t]
            df = len(docs)
            idf = math.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            norm = K1 * (1.0 - B + B * self.dl[docs] / self.avgdl)
            scores[docs] += idf * tf / (tf + norm)
        hit = np.flatnonzero(scores > 0)
        total = int(hit.size)
        if total == 0:
            return [], 0
        k = min(TOP_K, total)
        kth = np.partition(scores[hit], total - k)[total - k]
        cand = hit[scores[hit] >= kth]  # the top-k plus every tie at its edge
        # (score desc, shard asc, doc asc): SearchPhaseController order;
        # within a shard local doc order is insertion order
        if self.num_shards > 1:
            shard = np.array([self._shard_for_id(str(d), self.num_shards)
                              for d in cand.tolist()])
        else:
            shard = np.zeros(len(cand), np.int64)
        order = np.lexsort((cand, shard, -scores[cand]))[:k]
        return cand[order].tolist(), total


# ---------------------------------------------------------------------------
# HTTP
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, port: int):
        self.port = port

    def call(self, method: str, path: str, body=None, ndjson: bool = False):
        if body is not None and not isinstance(body, (bytes, str)):
            body = json.dumps(body)
        headers = {"Content-Type": "application/x-ndjson" if ndjson
                   else "application/json"}
        # one connection per call: the server drops a kept-alive
        # connection after 75 idle seconds, and phases last longer
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=1500)
        try:
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            raw = resp.read()
        finally:
            conn.close()
        if resp.status >= 300:
            raise RuntimeError(
                f"{method} {path} -> {resp.status}: {raw[:600]!r}")
        return json.loads(raw)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def node_stats(c: Client) -> dict:
    return next(iter(c.call("GET", "/_nodes/stats")["nodes"].values()))


def fused_dispatches(stats: dict) -> int:
    """Dispatches of the sharded fused Pallas program. A serving wave is
    fetched under the one name `serving.wave_program`, so the kernel table
    cannot say which arm a wave ran; the executable-cache counter of
    parallel/sharded._compiled_merged counts every fused dispatch."""
    c = stats["metrics"]["counters"]
    return int(c.get("es.jit.cache.sharded_fused.hits", 0)
               + c.get("es.jit.cache.sharded_fused.misses", 0))


def kernel_line(stats: dict) -> str:
    kernels = stats["device"]["utilization"]["kernels"]
    counters = stats["metrics"]["counters"]
    calls = {k: v["calls"] for k, v in sorted(kernels.items())
             if not k.startswith("build.")}

    def family(prefix):
        return {k[len(prefix):]: int(v) for k, v in sorted(counters.items())
                if k.startswith(prefix)}

    return (f"kernel calls {json.dumps(calls)} "
            f"topk tiers {json.dumps(family('es.search.topk.'))} "
            f"msearch arms {json.dumps(family('es.planner.decisions.'))} "
            f"fused dispatches {fused_dispatches(stats)} "
            f"es.jit.compiles {stats['device']['jit']['compiles']} "
            f"(compile {stats['device']['jit']['compile_time_in_millis'] / 1e3:.1f} s)")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def load(c: Client, lens, starts, tok, vocab: int, bulk_docs: int,
         num_shards: int) -> None:
    c.call("PUT", f"/{INDEX}", {
        "settings": {"number_of_shards": num_shards},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    term_strs = np.array([f"t{i}" for i in range(vocab)])
    n = len(lens)

    def payload(lo: int) -> bytes:
        hi = min(lo + bulk_docs, n)
        words = term_strs[tok[starts[lo]:starts[hi - 1] + lens[hi - 1]]]
        off, lines = 0, []
        for d in range(lo, hi):
            lines.append('{"index":{"_id":"%d"}}' % d)
            lines.append('{"body":"%s"}' % " ".join(words[off:off + lens[d]]))
            off += lens[d]
        return ("\n".join(lines) + "\n").encode()

    t0 = time.perf_counter()
    acked = 0
    with cf.ThreadPoolExecutor(1) as pool:  # build the next request meanwhile
        nxt = pool.submit(payload, 0)
        for lo in range(0, n, bulk_docs):
            body = nxt.result()
            if lo + bulk_docs < n:
                nxt = pool.submit(payload, lo + bulk_docs)
            res = c.call("POST", f"/{INDEX}/_bulk", body, ndjson=True)
            if res["errors"]:
                raise RuntimeError(f"bulk at {lo} reported errors: "
                                   f"{json.dumps(res['items'][:2])}")
            acked += len(res["items"])
    load_s = time.perf_counter() - t0
    if acked != n:
        raise RuntimeError(f"bulk acknowledged {acked} of {n} documents")
    say(f"load: {acked} docs in {load_s:.1f} s = {acked / load_s:.0f} "
        f"docs/s ({bulk_docs}-doc _bulk requests)")
    t0 = time.perf_counter()
    shards = c.call("POST", f"/{INDEX}/_refresh")["_shards"]
    refresh_s = time.perf_counter() - t0
    if shards["failed"]:  # a thrown refresh still answers 200
        raise RuntimeError(f"_refresh failed: {json.dumps(shards)[:600]}")
    say(f"refresh: {n} docs in {refresh_s:.1f} s = {n / refresh_s:.0f} "
        "docs/s")
    prof = c.call("GET", "/_refresh/profile")["profiles"][-1]
    top = sorted(prof["stages_ms"].items(), key=lambda kv: -kv[1])[:6]
    say(f"refresh stages ({prof['kind']}): "
        + ", ".join(f"{k} {v / 1e3:.1f} s" for k, v in top))
    fd = node_stats(c)["breakers"]["fielddata"]
    say(f"breaker: the packs charge {fd['estimated_size_in_bytes']} bytes "
        f"to one device, default fielddata limit "
        f"{fd['limit_size_in_bytes']} bytes")
    if load_s + refresh_s > 720:
        say(f"finding: load + refresh took {load_s + refresh_s:.0f} s, "
            "more than twelve minutes")


def hits_of(resp: dict):
    h = resp["hits"]
    return [int(x["_id"]) for x in h["hits"]], h["total"]


def check_answers(ref: Reference, checked: list) -> None:
    """checked: [(label, terms, response)] — ids, order and total."""
    ref.prepare({t for _, terms, _ in checked for t in terms})
    same = 0
    for label, terms, resp in checked:
        ids, total = hits_of(resp)
        want_ids, want_total = ref.top(terms)
        if (ids == want_ids and total == {"value": want_total,
                                          "relation": "eq"}):
            same += 1
        else:
            say(f"  mismatch {label} terms={terms}: got ids={ids} "
                f"total={total}; reference ids={want_ids} "
                f"total={want_total}")
    say(f"answers: {same}/{len(checked)} identical to the NumPy BM25 "
        f"reference (top-{TOP_K} ids, order, hits.total)")
    if same != len(checked):
        fail(f"{len(checked) - same} of {len(checked)} answers differ "
             "from the reference")


def msearch(c: Client, queries: list[list[int]]) -> list[dict]:
    lines = []
    for terms in queries:
        lines.append("{}")
        lines.append(json.dumps(search_body(terms)))
    t0 = time.perf_counter()
    res = c.call("POST", f"/{INDEX}/_msearch", "\n".join(lines) + "\n",
                 ndjson=True)["responses"]
    sec = time.perf_counter() - t0
    bad = [r for r in res if r.get("status") != 200]
    if bad:
        raise RuntimeError(f"_msearch: {len(bad)} sub-responses failed: "
                           f"{json.dumps(bad[0])[:400]}")
    say(f"_msearch: {len(queries)} queries in {sec:.2f} s "
        "(first call: compiles included)")
    return res


def solo_searches(c: Client, queries: list[list[int]]) -> list[dict]:
    out = []
    for i, terms in enumerate(queries):
        if i == 1:
            say("before 2nd _search: " + kernel_line(node_stats(c)))
        t0 = time.perf_counter()
        out.append(c.call("POST", f"/{INDEX}/_search", search_body(terms)))
        say(f"_search {i + 1}/{len(queries)} ({len(terms)} terms): "
            f"{(time.perf_counter() - t0) * 1e3:.0f} ms")
        if i == 1:
            say("after  2nd _search: " + kernel_line(node_stats(c)))
    return out


def concurrent_searches(port: int, queries: list[list[int]]) -> list:
    """All requests in flight at once, one connection each. -> responses,
    with the exception in the place of a request that failed."""
    def one(terms):
        try:
            return Client(port).call("POST", f"/{INDEX}/_search",
                                     search_body(terms))
        except Exception as e:  # noqa: BLE001 - counted and failed below
            return e

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(len(queries)) as pool:
        out = list(pool.map(one, queries))
    bad = [r for r in out if isinstance(r, Exception)]
    say(f"concurrent _search: {len(queries) - len(bad)}/{len(queries)} "
        f"answered in {time.perf_counter() - t0:.2f} s")
    if bad:
        fail(f"{len(bad)} of {len(queries)} concurrent requests failed; "
             f"first: {type(bad[0]).__name__}: {bad[0]}")
    return out


def check_device(stats: dict, want_count: int) -> dict:
    mem = stats["device"]["memory"]
    device = {"platform": mem["backend"], "kind": mem["device_kind"],
              "count": mem["device_count"]}
    say(f"device (as the server reports it): {json.dumps(device)}")
    if device["platform"] != "tpu":
        fail(f"the server ran on platform {device['platform']!r}, not tpu")
    if device["count"] != want_count:
        fail(f"the server sees {device['count']} devices, not {want_count}")
    return device


def run_requests(c: Client, port: int, ref: Reference, queries,
                 chips: int) -> dict:
    solo_q, ms_q, conc_q = queries[:8], queries[8:520], queries[520:584]
    solo = solo_searches(c, solo_q)
    # the fused `_msearch` arm is reached through the serving front end
    # only: with serving off, REST runs the sub-searches one by one
    c.call("PUT", "/_cluster/settings",
           {"persistent": {"serving.enabled": True}})
    ms = msearch(c, ms_q)
    after_ms = node_stats(c)
    say("after _msearch: " + kernel_line(after_ms))
    pick = np.random.default_rng(7).choice(len(ms_q), size=32, replace=False)
    checked = ([(f"_search[{i}]", q, r)
                for i, (q, r) in enumerate(zip(solo_q, solo))]
               + [(f"_msearch[{i}]", ms_q[i], ms[i]) for i in sorted(pick)])
    if chips == 1:
        conc = concurrent_searches(port, conc_q)
        serving = c.call("GET", "/_serving/stats")["serving"]
        say(f"serving: waves {serving['waves']} coalesced "
            f"{serving['coalesced']} term_packed {serving['term_packed']} "
            f"avg wave size {serving['wave']['avg_size']}")
        if not serving["coalesced"]:
            fail("no serving wave coalesced more than one request")
        checked += [(f"concurrent[{i}]", conc_q[i], conc[i])
                    for i in range(0, len(conc_q), 8)
                    if not isinstance(conc[i], Exception)]
    stats = node_stats(c)
    say("at the end: " + kernel_line(stats))
    check_answers(ref, checked)

    if not fused_dispatches(after_ms):
        fail("the fused Pallas pipeline (parallel/sharded._compiled_merged) "
             "served no _msearch wave")
    if chips == 4:
        per_dev = stats["device"]["memory"]["devices"]
        say("bytes held per device: " + json.dumps(per_dev))
        # the allocator's own count where the backend has one (tpu)
        held = [d.get("bytes_in_use", d["live_bytes"]) for d in per_dev]
        if len(held) != 4 or any(h * 8 < sum(held) for h in held):
            fail("the pack is not spread over four devices")
    return check_device(stats, chips)


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # 1<<19: half of C1's 1,000,000. Not load time (1M loads and
    # refreshes in 5.5 minutes): the default fielddata breaker refuses the
    # 12.06 GB pack of 1M docs on one chip, and with the limit raised a
    # cold-cache run spent 640 s of its 1,009 s in XLA compiles, too near
    # this script's 1,200 s limit (PERF.md, PR 22)
    ap.add_argument("--docs", type=int, default=1 << 19)
    ap.add_argument("--vocab", type=int, default=100_000)
    ap.add_argument("--doc-len", type=int, default=40)
    ap.add_argument("--bulk-docs", type=int, default=5_000)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if not os.path.isdir(PKG):
        print(f"chip_smoke: {PKG} is missing — this script drives the "
              "repo's server and is nothing without it", file=sys.stderr)
        return 2

    t_start = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    lens, tok = build_corpus(rng, args.docs, args.vocab, args.doc_len)
    starts = np.concatenate([[0], np.cumsum(lens[:-1])])
    queries = sample_queries(rng, lens, starts, tok, 584)
    say(f"corpus: {args.docs} docs, {int(lens.sum())} tokens, vocab "
        f"{args.vocab}, seed {args.seed}, made in "
        f"{time.perf_counter() - t_start:.1f} s")

    out_dir = os.path.join(HERE, "chiprun_out")
    data_dir = os.path.join(HERE, ".smoke_data")
    os.makedirs(out_dir, exist_ok=True)
    shutil.rmtree(data_dir, ignore_errors=True)
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(HERE, ".jax_cache"))

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    n_cached = cache_entries()
    say(f"compile cache: {cache_dir} holds {n_cached} entries at the start")

    port = free_port()
    log_path = os.path.join(out_dir, f"smoke_server_{args.chips}chip.log")
    # the server's log names every XLA compile and its seconds
    env = dict(os.environ, PYTHONPATH=HERE, PYTHONFAULTHANDLER="1",
               JAX_LOG_COMPILES="1")
    with open(log_path, "wb") as log:
        server = subprocess.Popen(
            [sys.executable, "-m", "elasticsearch_tpu.rest.server",
             "--port", str(port), "--data-path", data_dir],
            cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT)
    device = None
    try:
        c = Client(port)
        t0 = time.perf_counter()
        while True:
            try:
                c.call("GET", "/")
                break
            except (ConnectionError, OSError):
                if server.poll() is not None:
                    raise RuntimeError(
                        f"the server exited with {server.returncode}")
                if time.perf_counter() - t0 > 300:
                    raise RuntimeError("the server did not come up in 300 s")
                time.sleep(0.5)
        say(f"server: up on port {port} after "
            f"{time.perf_counter() - t0:.1f} s")
        ref = Reference(lens, tok, args.chips)
        load(c, lens, starts, tok, args.vocab, args.bulk_docs, args.chips)
        say("accumulator: "
            + node_stats(c)["indexing"].get("accumulator", "?"))
        t_q = time.perf_counter()
        device = run_requests(c, port, ref, queries, args.chips)
        say(f"set-up: request phases (compiles included) took "
            f"{time.perf_counter() - t_q:.1f} s; compile cache "
            f"{n_cached} -> {cache_entries()} entries; whole run "
            f"{time.perf_counter() - t_start:.1f} s")
    except Exception as e:  # noqa: BLE001 - reported, and the run fails
        fail(f"{type(e).__name__}: {e}")
    finally:
        if server.poll() is not None:
            fail(f"the server died with exit code {server.returncode}")
        server.terminate()
        try:
            server.wait(timeout=30)
        except subprocess.TimeoutExpired:
            server.kill()
            server.wait()
        shutil.rmtree(data_dir, ignore_errors=True)
    with open(log_path, errors="replace") as f:
        log_lines = f.read().splitlines()
    slow = sorted(((float(m.group(2)), m.group(1)) for m in (
        re.search(r"Finished XLA compilation of (\S+) in ([0-9.]+) sec", ln)
        for ln in log_lines) if m), reverse=True)
    say(f"compiles: {len(slow)} programs, {sum(t for t, _ in slow):.0f} s; "
        "slowest: " + ", ".join(f"{n} {t:.0f} s" for t, n in slow[:8]))
    if FAILURES:
        tail = "\n".join([ln for ln in log_lines
                          if "jax._src." not in ln][-40:])
        print(f"--- server log tail ({log_path}) ---\n{tail}",
              file=sys.stderr)
        print(f"chip_smoke: {len(FAILURES)} check(s) failed: {FAILURES}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
