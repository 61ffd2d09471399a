"""chip_smoke.py's own parts that need no chip: its NumPy BM25 reference
agrees with the repo's independent per-document oracle, and the script is
nothing without the repo around it."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from elasticsearch_tpu.index.mappings import Mappings
from reference_scorer import Oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(3)
    lens, tok = chip_smoke.build_corpus(rng, 400, 60, 40)
    starts = np.concatenate([[0], np.cumsum(lens[:-1])])
    queries = chip_smoke.sample_queries(rng, lens, starts, tok, 12)
    docs = [{"body": " ".join(f"t{t}" for t in tok[s:s + n])}
            for s, n in zip(starts, lens)]
    oracle = Oracle(docs, Mappings({"properties": {"body": {"type": "text"}}}))
    return lens, tok, queries, oracle


@pytest.mark.parametrize("qi", range(12))
def test_numpy_reference_agrees_with_the_per_document_oracle(corpus, qi):
    lens, tok, queries, oracle = corpus
    ref = chip_smoke.Reference(lens, tok, num_shards=1)
    ref.prepare({t for q in queries for t in q})
    terms = queries[qi]
    scores, match = oracle.eval(
        chip_smoke.search_body(terms)["query"])
    want = sorted(match, key=lambda d: (-round(scores[d], 9), d))[:10]
    ids, total = ref.top(terms)
    assert total == len(match)
    assert ids == want


def test_four_shard_reference_reorders_only_ties(corpus):
    lens, tok, queries, oracle = corpus
    one = chip_smoke.Reference(lens, tok, num_shards=1)
    four = chip_smoke.Reference(lens, tok, num_shards=4)
    terms = {t for q in queries for t in q}
    one.prepare(terms)
    four.prepare(terms)
    for q in queries:
        scores, _ = oracle.eval(chip_smoke.search_body(q)["query"])
        (a, ta), (b, tb) = one.top(q), four.top(q)
        assert ta == tb
        assert ([round(scores[d], 9) for d in a]
                == [round(scores[d], 9) for d in b])
        shard = [four._shard_for_id(str(d), 4) for d in b]
        key = [(-round(scores[d], 9), s, d) for d, s in zip(b, shard)]
        assert key == sorted(key)


def test_script_alone_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
