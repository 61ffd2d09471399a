"""Kinds of deployment, found by name: a configuration's `kind` names
`benchmark/kinds/<kind>.py`, which makes the corpus, the pool, the request
bodies, the index and its `_bulk` payloads, keeps what an answer says and
judges the answers. What the first kind, `bm25_match`, makes for the accepted
configurations is byte for byte what the harness made before kinds were
names (digests taken from that harness, at commit 882cd0f); its comparison
gives the same numbers; and a second kind that differs in every seam, which
lives in a scratch root alone, runs end to end against a stub and against the
repo's real server on the CPU."""

import hashlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
from checks import BENCH, REPO, harness  # noqa: E402
from test_benchmark_harness import StubServer, _real_server, add_files  # noqa: E402

from benchlib.reference import Reference  # noqa: E402
from benchlib.stats import Request  # noqa: E402


def _sha(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


def _arrays_sha(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# -- bm25_match makes what the harness made ----------------------------------

SEEDS = (4200000017, 2147483659)
# "<cell>:<seed>": sha256 of the corpus arrays (lens, tok, starts), the pool
# (JSON), every request body (joined by newlines), the create-index body and
# the first and last `_bulk` payload, as the harness of commit 882cd0f made
# them
PARENT = {
    "passage.solo.c1:4200000017": {
        "corpus": "b3eb107ae376a0cc403dfd2f56422523a90aefb712ca765d5411da83d4efccf7",
        "pool": "d477985c9d06439830b17f9b3d5c0959af0c5a48e09d5eb82ea14c2bdc8fe834",
        "bodies": "5d761bf99e7f7c0f7e3ee114d31a98e1ce61ee25a420af8830d7f31e71634c1c",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0730c50ccd4351177544e96d9f6c6d10e4a2643c1d49f29416356c16bd2f57ce",
        "bulk_last": "0fd9528bdd1200db4e6f6cea8c0510177d766a19c03e7c66dd7bafd633b6a8b0",
    },
    "passage.solo.c8:4200000017": {
        "corpus": "b3eb107ae376a0cc403dfd2f56422523a90aefb712ca765d5411da83d4efccf7",
        "pool": "d477985c9d06439830b17f9b3d5c0959af0c5a48e09d5eb82ea14c2bdc8fe834",
        "bodies": "5d761bf99e7f7c0f7e3ee114d31a98e1ce61ee25a420af8830d7f31e71634c1c",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0730c50ccd4351177544e96d9f6c6d10e4a2643c1d49f29416356c16bd2f57ce",
        "bulk_last": "0fd9528bdd1200db4e6f6cea8c0510177d766a19c03e7c66dd7bafd633b6a8b0",
    },
    "passage-4chip.solo.c8:4200000017": {
        "corpus": "a437291f0c99d82a595fbd54bd9d8fe4fdf2e69046c46efd81776ae7bb56163f",
        "pool": "c827c171f2e2a9f2cb373bbfc5b18f2d95cca6625eb358107a065c78eafcd8a9",
        "bodies": "b553b33edc293c065b397ac821384a574473704fd76b5a550ff6c21501417f24",
        "index": "babc8734724b6e2f0f9910fdd3a2df7dd1dc3ff607bc19f18c03442bcbdf7895",
        "bulk_first": "31a24bf9f3f230a0f67120dbd2d4ff78ce7feeb693b107f3b7807eaadc2b0940",
        "bulk_last": "6c714ddb969a38694eca5ef2968690c58b8dd24562218ed7a9c6546ceccd86a4",
    },
    "passage.wave.c64:4200000017": {
        "corpus": "b3eb107ae376a0cc403dfd2f56422523a90aefb712ca765d5411da83d4efccf7",
        "pool": "f51f6cd8d79d87b8f7331d7ca6ba2b6ddada0daa387a005e34dd18b32c12182e",
        "bodies": "4584b065e25ef2cc5276586be6986298b1e837ac6a43ca5fd3e500ee607f1d2b",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0730c50ccd4351177544e96d9f6c6d10e4a2643c1d49f29416356c16bd2f57ce",
        "bulk_last": "0fd9528bdd1200db4e6f6cea8c0510177d766a19c03e7c66dd7bafd633b6a8b0",
    },
    "passage.solo.fresh:4200000017": {
        "corpus": "b3eb107ae376a0cc403dfd2f56422523a90aefb712ca765d5411da83d4efccf7",
        "pool": "6f16c5a4dc1a507c12b6341275702acf0806007918400a58ba1e03c922e43103",
        "bodies": "9522641846741928f9a11ca5f4909167a0d5da50ccc20d8b18f765ea43991c01",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0730c50ccd4351177544e96d9f6c6d10e4a2643c1d49f29416356c16bd2f57ce",
        "bulk_last": "0fd9528bdd1200db4e6f6cea8c0510177d766a19c03e7c66dd7bafd633b6a8b0",
    },
    "passage.solo.c1:2147483659": {
        "corpus": "063ad6923e4641a65acde57d7f250df66bbaa4e5d1fd14d100422db7052c4c57",
        "pool": "9697d691411a53175dfec672c9c9dcd219def8db1e410d1ec361817c296bf264",
        "bodies": "3aae3baa0f2080cb038257b5d1207815a515a8627ef004fb655201c9e817cf05",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0cbd4952dc69fbb27a3980b478bd6555710ecdaefa65c1311a3407b8c728ac99",
        "bulk_last": "3c6a56e0b40f06764863d062057bb021b1728d03ff9f55745c0b9279096dc741",
    },
    "passage.solo.c8:2147483659": {
        "corpus": "063ad6923e4641a65acde57d7f250df66bbaa4e5d1fd14d100422db7052c4c57",
        "pool": "9697d691411a53175dfec672c9c9dcd219def8db1e410d1ec361817c296bf264",
        "bodies": "3aae3baa0f2080cb038257b5d1207815a515a8627ef004fb655201c9e817cf05",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0cbd4952dc69fbb27a3980b478bd6555710ecdaefa65c1311a3407b8c728ac99",
        "bulk_last": "3c6a56e0b40f06764863d062057bb021b1728d03ff9f55745c0b9279096dc741",
    },
    "passage-4chip.solo.c8:2147483659": {
        "corpus": "33faf8761798f2a0df24833e86a92f155be29f464c47fea738b0ef3cf4ec6f4c",
        "pool": "c2418a3c90293266344c640c83dacc0157d1361171e950032e7c3eb3d8ce7918",
        "bodies": "39aa9d6c1c51b38e448801be7883726bbb8f9f75c3a0fab8dfed122b54204003",
        "index": "babc8734724b6e2f0f9910fdd3a2df7dd1dc3ff607bc19f18c03442bcbdf7895",
        "bulk_first": "e7c56b3d5ef599ffb90bbc2b788d1829a8aaeb47bced23f86308c67b27694d93",
        "bulk_last": "cd7a6692bb5ebf26f395892183bf887df92bc3614fb421fce563a0080924ce1f",
    },
    "passage.wave.c64:2147483659": {
        "corpus": "063ad6923e4641a65acde57d7f250df66bbaa4e5d1fd14d100422db7052c4c57",
        "pool": "99dba701550c93a52b9fc39a5f68670c9c5ecc1d2137e5a6a2ef8b5a0f4e23ad",
        "bodies": "94f57559c293876a7bdd078aea3770b9e541ce0c4a73b3ac1f664de6825be199",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0cbd4952dc69fbb27a3980b478bd6555710ecdaefa65c1311a3407b8c728ac99",
        "bulk_last": "3c6a56e0b40f06764863d062057bb021b1728d03ff9f55745c0b9279096dc741",
    },
    "passage.solo.fresh:2147483659": {
        "corpus": "063ad6923e4641a65acde57d7f250df66bbaa4e5d1fd14d100422db7052c4c57",
        "pool": "f179510d802fae725dce0815da26ceda956a5a8489ee4286194734d41fa1321d",
        "bodies": "d87ee3e251f2cadf33b222a156c651fb8ad035e1bbb94ddb01084895b6444227",
        "index": "19cc8cc60b0bcf48768aa993489c2624bcb83c67c8990c406bc56ba9b168e164",
        "bulk_first": "0cbd4952dc69fbb27a3980b478bd6555710ecdaefa65c1311a3407b8c728ac99",
        "bulk_last": "3c6a56e0b40f06764863d062057bb021b1728d03ff9f55745c0b9279096dc741",
    },
}
ACCEPTED = ("passage.solo.c1", "passage.solo.c8", "passage-4chip.solo.c8",
            "passage.wave.c64", "passage.solo.fresh")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", ACCEPTED)
def test_an_accepted_cell_sends_what_it_sent_before_kinds_were_names(cell,
                                                                     seed):
    spec = harness.resolve(REPO, cell)
    assert "kind" not in spec["config"]          # the files stay as they were
    kind, config = spec["kind"], spec["config"]
    assert kind.__file__ == os.path.join(BENCH, "kinds", "bm25_match.py")
    corpus, pool, bodies = harness.make_inputs(spec, seed)
    payload = kind.bulk(corpus, config)
    n, per = corpus.n_docs, int(config["bulk_docs"])
    last = (n - 1) // per * per
    got = {"corpus": _arrays_sha(corpus.lens, corpus.tok, corpus.starts),
           "pool": _sha(json.dumps(pool).encode()),
           "bodies": _sha(b"\n".join(bodies)),
           "index": _sha(json.dumps(kind.index_body(config)).encode()),
           "bulk_first": _sha(payload(0, min(per, n))),
           "bulk_last": _sha(payload(last, n))}
    assert got == PARENT[f"{cell}:{seed}"]


STUB_SEED = 3000000019
# what the comparison of commit 882cd0f gave on `_stub_answers`, on one
# shard and on four: correct, compared, queries, numbers
PARENT_VERDICT = {
    "correct": False, "compared": 30, "queries": 24,
    "numbers": {"total_wrong": 6, "rank_gap": 0.05703063654111308,
                "score_gap": 0.3933110272042008, "order_wrong": 1,
                "repeat_diff": 6, "ids_differ": 5}}


def _stub_answers(corpus, pool, shards):
    """Answers the reference itself gives, one query in five bent each way:
    a total off by one, scores scaled, two ids swapped, two scores swapped,
    a second answer whose scores differ; and a failed request now and
    then."""
    ref = Reference(corpus.lens, corpus.tok, shards)
    ref.prepare({t for q in pool for t in q})
    out = []
    for q, terms in enumerate(pool):
        ids, scores, total = ref.top(terms, 10)
        tot = {"value": total, "relation": "eq"}
        if q % 5 == 1:
            tot = {"value": total + 1, "relation": "eq"}
        elif q % 5 == 2:
            scores = [s * (1 + 1e-4 * (q % 7)) for s in scores]
        elif q % 5 == 3 and len(ids) > 4:
            ids = ids[:3] + [ids[4], ids[3]] + ids[5:]
        elif q % 5 == 4 and len(ids) > 2:
            scores = [scores[1], scores[0]] + scores[2:]
        t = q * 1e-3
        out.append(Request(q, t, t + 5e-4, 200, 1.0, ids, scores, tot))
        if q % 5 == 0:
            out.append(Request(q, t, t + 7e-4, 200, 1.0, ids,
                               [s * (1 + 3e-5) for s in scores], tot))
        if q % 9 == 0:
            out.append(Request(q, t, t + 9e-4, 503))
    return out


@pytest.mark.parametrize("shards", [1, 4])
def test_bm25_match_judges_fixed_answers_as_the_comparison_did(shards):
    config = harness.read_json(
        os.path.join(BENCH, "configs", "msmarco-passage-1shard.json"))
    config.update(documents=3000, number_of_shards=shards)
    config["corpus"] = dict(config["corpus"], vocab=2000)
    traffic = {"pool": 40, "check_sample": 24}
    kind = harness.kind_module(BENCH, "bm25_match")
    corpus = kind.corpus(STUB_SEED, config)
    pool = kind.pool(STUB_SEED, corpus, config, traffic)
    v = kind.judge(STUB_SEED, corpus, pool,
                   _stub_answers(corpus, pool, shards), config, traffic)
    assert {k: v[k] for k in ("correct", "compared", "queries")} == {
        k: PARENT_VERDICT[k] for k in ("correct", "compared", "queries")}
    assert {k: n["value"] for k, n in v["numbers"].items()} \
        == PARENT_VERDICT["numbers"]
    assert {k: n["limit"] for k, n in v["numbers"].items()} == dict(
        config["limits"], ids_differ=None)


def test_a_request_keeps_of_an_answer_what_it_kept():
    """One `json.loads` and the same fields: `took` as a float, the ids as
    ints, the scores and the total as answered."""
    kind = harness.kind_module(BENCH, "bm25_match")
    res = {"took": 3, "hits": {"total": {"value": 7, "relation": "eq"},
                               "hits": [{"_id": "12", "_score": 2.5},
                                        {"_id": "4", "_score": 1.25}]}}
    r = Request(5, 0.0, 0.001, 200, *kind.digest(res))
    assert (r.took_ms, r.ids, r.scores, r.total, r.answer) == (
        3.0, [12, 4], [2.5, 1.25], {"value": 7, "relation": "eq"}, None)
    for bad in ({"took": 1}, {"took": 1, "hits": {"hits": [{"_id": "x"}]}}):
        with pytest.raises((KeyError, ValueError, TypeError)):
            kind.digest(bad)


# -- a kind is a name the harness resolves -----------------------------------

def _root_with(tmp_path, config_update: dict, kind_src: dict | None = None):
    """A scratch copy of the benchmark with one more configuration (the first
    one, cut small and updated), a mix and a cell `k.c2`; `kind_src` adds
    files under `benchmark/kinds/`."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "elasticsearch_tpu").mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name, src in (kind_src or {}).items():
        (root / "benchmark" / "kinds" / f"{name}.py").write_text(src)
    config = harness.read_json(
        os.path.join(BENCH, "configs", "msmarco-passage-1shard.json"))
    config.update(name="k-config", source="scratch", documents=800)
    config["corpus"] = dict(config["corpus"], vocab=500)
    config.update(config_update)
    add_files(root, config,
              {"name": "closed-c2", "clients": 2, "rate": None, "pool": 24,
               "warmup_max_passes": 2, "check_sample": 24},
              {"name": "k.c2", "config": "k-config", "traffic": "closed-c2",
               "chips": 1})
    return str(root)


def test_no_kind_and_the_first_kinds_name_resolve_alike(tmp_path):
    none = harness.resolve(_root_with(tmp_path / "a", {}), "k.c2")
    named = harness.resolve(
        _root_with(tmp_path / "b", {"kind": "bm25_match"}), "k.c2")
    assert os.path.basename(none["kind"].__file__) == "bm25_match.py"
    assert os.path.basename(named["kind"].__file__) == "bm25_match.py"
    a = harness.make_inputs(none, 11)
    b = harness.make_inputs(named, 11)
    assert a[1] == b[1] and a[2] == b[2]
    assert np.array_equal(a[0].tok, b[0].tok)


def test_an_unknown_kind_is_an_error_and_not_a_default(tmp_path, capsys):
    root = _root_with(tmp_path, {"kind": "no_such_kind"})
    with pytest.raises(harness.BenchError,
                       match=r"no kind 'no_such_kind': benchmark/kinds/ has "
                             r"\['bm25_match'\]"):
        harness.resolve(root, "k.c2")
    # through the command: exit code 1, the kinds named, nothing on stdout
    rc = harness.main(["--workload", "k.c2", "--seed", "4200000001",
                       "--seconds", "1", "--trace", "0"], spec_root=root,
                      program_root=root, require_chip=False,
                      server_factory=StubServer)
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "no kind 'no_such_kind'" in out.err
    # the cells of the other kinds resolve as before
    assert harness.resolve(root, "passage.solo.c1")["kind"].judge


def test_a_kind_that_lacks_a_seam_is_an_error(tmp_path):
    src = open(os.path.join(BENCH, "kinds", "bm25_match.py")).read()
    src = src.replace("def judge(", "def _judge(")
    root = _root_with(tmp_path, {"kind": "half"}, {"half": src})
    with pytest.raises(harness.BenchError, match=r"kind 'half' .* lacks \['judge'\]"):
        harness.resolve(root, "k.c2")


# -- a second kind, in a scratch root alone ------------------------------------

KEYWORD_TERMS = '''"""A kind for the tests alone: documents of two keyword fields, `host` and
`status`; a query is a `term` on `host` with `size: 0` and a `terms`
aggregation over `status`; the reference counts with NumPy."""

from __future__ import annotations

import json
from dataclasses import dataclass

import numpy as np


@dataclass
class Corpus:
    host: np.ndarray       # [n_docs] host number
    status: np.ndarray     # [n_docs] index into `statuses`
    statuses: list

    @property
    def n_docs(self):
        return len(self.host)


def corpus(seed, config):
    spec = config["corpus"]
    statuses = sorted(spec["statuses"])
    p = np.array([spec["statuses"][s] for s in statuses], np.float64)
    rng = np.random.default_rng([int(seed), 0])
    n = int(config["documents"])
    return Corpus(rng.integers(0, int(spec["hosts"]), n),
                  rng.choice(len(statuses), n, p=p / p.sum()), statuses)


def pool(seed, corpus, config, traffic):
    rng = np.random.default_rng([int(seed), 1])
    hosts = int(config["corpus"]["hosts"])
    return rng.integers(0, hosts, int(traffic["pool"])).tolist()


def body(query, config):
    return {"size": 0, "track_total_hits": True,
            "query": {"term": {"host": f"h{query}"}},
            "aggs": {"by_status": {"terms": {
                "field": "status",
                "size": len(config["corpus"]["statuses"])}}}}


def index_body(config):
    return {"settings": {"number_of_shards": int(config["number_of_shards"]),
                         "number_of_replicas": 0},
            "mappings": {"properties": {"host": {"type": "keyword"},
                                        "status": {"type": "keyword"}}}}


def bulk(corpus, config):
    def payload(lo, hi):
        lines = []
        for d in range(lo, hi):
            lines.append('{"index":{"_id":"%d"}}' % d)
            lines.append(json.dumps(
                {"host": f"h{corpus.host[d]}",
                 "status": corpus.statuses[corpus.status[d]]}))
        return ("\\n".join(lines) + "\\n").encode()
    return payload


def digest(res):
    buckets = res["aggregations"]["by_status"]["buckets"]
    return (float(res["took"]), None, None, res["hits"]["total"],
            [[b["key"], b["doc_count"]] for b in buckets])


def expected(corpus, host):
    """-> (hits.total, buckets in (doc_count desc, key asc) order)."""
    mine = corpus.status[corpus.host == host]
    counts = np.bincount(mine, minlength=len(corpus.statuses))
    buckets = sorted(([corpus.statuses[i], int(c)]
                      for i, c in enumerate(counts) if c),
                     key=lambda b: (-b[1], b[0]))
    return {"value": int(mine.size), "relation": "eq"}, buckets


def judge(seed, corpus, pool, requests, config, traffic):
    """Every answered request, against exact counts."""
    answered = [r for r in requests if r.ok]
    want = {q: expected(corpus, pool[q]) for q in {r.query for r in answered}}
    total_wrong = sum(r.total != want[r.query][0] for r in answered)
    buckets_wrong = sum(r.answer != want[r.query][1] for r in answered)
    limits = config["limits"]
    numbers = {"total_wrong": {"value": total_wrong,
                               "limit": limits["total_wrong"]},
               "buckets_wrong": {"value": buckets_wrong,
                                 "limit": limits["buckets_wrong"]}}
    correct = bool(answered) and all(
        n["value"] <= n["limit"] for n in numbers.values())
    return {"correct": correct, "compared": len(answered),
            "queries": len(want), "numbers": numbers}
'''

KEYWORD_CONFIG = {
    "name": "tiny-keyword", "kind": "keyword_terms", "source": "scratch",
    "chips": 1, "number_of_shards": 1, "documents": 2000,
    "source_documents": 100000,
    "reduced": {"documents": "scratch"}, "assumed": {"why": "scratch"},
    "corpus": {"hosts": 40, "statuses": {"200": 80, "301": 5, "404": 10,
                                         "500": 3, "503": 2}},
    "bulk_docs": 500,
    "settings": {"indices.requests.cache.enable": False},
    "settings_why": {"indices.requests.cache.enable": "scratch: every "
                     "request reaches the aggregation"},
    "guarantees": ["hits.total and every terms bucket are exact"],
    "limits": {"total_wrong": 0, "buckets_wrong": 0}}


@pytest.fixture()
def keyword_root(tmp_path):
    """A scratch copy of the benchmark grown by files alone: the kind
    `keyword_terms`, a configuration of it, a mix and a cell."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "elasticsearch_tpu").mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    (root / "benchmark" / "kinds" / "keyword_terms.py").write_text(KEYWORD_TERMS)
    add_files(root, KEYWORD_CONFIG,
              {"name": "closed-c2", "clients": 2, "rate": None, "pool": 30,
               "warmup_max_passes": 3, "check_sample": 30},
              {"name": "keyword.c2", "config": "tiny-keyword",
               "traffic": "closed-c2", "chips": 1})
    return str(root)


def test_a_second_kind_added_as_files_passes_every_check(keyword_root):
    checks.check_all(keyword_root)
    spec = harness.resolve(keyword_root, "keyword.c2")
    kind, config = spec["kind"], spec["config"]
    assert os.path.basename(kind.__file__) == "keyword_terms.py"
    corpus, pool, bodies = harness.make_inputs(spec, 5)
    first = json.loads(bodies[0])
    assert first["size"] == 0 and "term" in first["query"]
    assert kind.index_body(config)["mappings"]["properties"]["host"] == {
        "type": "keyword"}
    lines = kind.bulk(corpus, config)(0, 3).decode().splitlines()
    assert len(lines) == 6 and json.loads(lines[1])["host"].startswith("h")
    # the repo's own cells are still bm25_match's
    assert os.path.basename(harness.resolve(
        keyword_root, "passage.solo.c1")["kind"].__file__) == "bm25_match.py"


class KeywordStub(StubServer):
    """The stub's REST surface, answering `keyword_terms` requests from what
    `_bulk` loaded. `fault` breaks the timed path underneath."""

    def post(self, path, body):
        if path.endswith("/_bulk"):
            lines = body.decode().splitlines()
            items = []
            for meta, doc in zip(lines[0::2], lines[1::2]):
                i = int(json.loads(meta)["index"]["_id"])
                self.docs[i] = json.loads(doc)
                items.append({"index": {"_id": str(i), "status": 201}})
            return {"errors": False, "items": items}
        if path.endswith("/_refresh"):
            return {"_shards": {"total": 1, "successful": 1, "failed": 0}}
        if path.endswith("/_search"):
            return self.search(json.loads(body))
        if path.startswith("/c1"):
            assert json.loads(body)["mappings"]["properties"]["status"] == {
                "type": "keyword"}
            return {"acknowledged": True}
        return super().post(path, body)

    def search(self, body):
        host = body["query"]["term"]["host"]
        counts: dict = {}
        with self.lock:
            self.searches += 1
            for d in self.docs.values():
                if d["host"] == host:
                    counts[d["status"]] = counts.get(d["status"], 0) + 1
        buckets = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
        total = sum(counts.values())
        if self.fault == "bucket_off_by_one" and buckets:
            buckets[-1] = (buckets[-1][0], buckets[-1][1] + 1)
        elif self.fault == "total_off_by_one":
            total += 1
        return {"took": 1, "timed_out": False,
                "hits": {"total": {"value": total, "relation": "eq"},
                         "max_score": None, "hits": []},
                "aggregations": {"by_status": {
                    "doc_count_error_upper_bound": 0, "sum_other_doc_count": 0,
                    "buckets": [{"key": k, "doc_count": n}
                                for k, n in buckets]}}}


def _drive_keyword(root, **stub_kw):
    def factory(*a, **kw):
        return KeywordStub(*a, **kw, **stub_kw)

    return harness.run_cell("keyword.c2", seed=2**31 + 91, seconds=1.0,
                            traced=False, spec_root=root, program_root=root,
                            require_chip=False, server_factory=factory)


def test_a_second_kind_runs_against_the_stub_and_is_correct(keyword_root):
    res = _drive_keyword(keyword_root)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == checks.END_TO_END
    assert set(res["compared"]) == {"total_wrong", "buckets_wrong"}
    assert res["window"]["answers_compared"] == res["attempted"] > 0
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("fault,number", [("bucket_off_by_one", "buckets_wrong"),
                                          ("total_off_by_one", "total_wrong")])
def test_a_second_kind_with_an_answer_altered_is_not_correct(keyword_root,
                                                             fault, number):
    res = _drive_keyword(keyword_root, fault=fault)
    assert res["correct"] is False
    assert res["compared"][number]["value"] > res["compared"][number]["limit"]


def test_a_second_kind_on_the_real_server_on_the_cpu(keyword_root, tmp_path):
    """The kind's mapping, payloads and bodies against the repo's own server
    at a small size: every answer's total and buckets are the exact counts,
    nothing compiles in the window."""
    res = _real_server(keyword_root, tmp_path, "keyword.c2", 1.5,
                       require_chip=False)
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert res["window"]["compiles_in_window"] == 0
    assert res["window"]["request_cache_hits"] == 0
    assert res["window"]["answers_compared"] >= 30
    assert res["compared"] == {"total_wrong": {"value": 0, "limit": 0},
                               "buckets_wrong": {"value": 0, "limit": 0}}
