"""The write path that loads four shards inside one run (PR 29): one WAL sync
a `_bulk` request, the shards of a refresh built at once and each on its own
device, and the one setting that bounds how many."""

import os

import jax
import numpy as np
import pytest

from elasticsearch_tpu.common.settings import ClusterSettings, default_cluster_settings
from elasticsearch_tpu.engine import Engine
from elasticsearch_tpu.index import device_build
from elasticsearch_tpu.index.mappings import Mappings
from elasticsearch_tpu.index.pack import PackBuilder
from elasticsearch_tpu.parallel.spmd import make_mesh
from elasticsearch_tpu.parallel.stacked import (
    build_stacked_pack_routed, default_shard_builders, route_docs)
from elasticsearch_tpu.telemetry import TRACER, metrics
from elasticsearch_tpu.utils.errors import IllegalArgumentError

BODY = {"properties": {"body": {"type": "text"}}}
SETTING = "indexing.refresh.shard_builders"


def _ops(lo, hi, index="t"):
    return [("index", index, str(i), {"body": f"alpha w{i % 13} text {i}"})
            for i in range(lo, hi)]


@pytest.fixture()
def fsyncs(monkeypatch):
    """Every `os.fsync` the engine makes, counted."""
    calls = []
    real = os.fsync

    def counted(fd):
        calls.append(fd)
        return real(fd)

    monkeypatch.setattr(os, "fsync", counted)
    return calls


# -- (a) durability: one sync a request, nothing acknowledged unsynced --------

def test_a_bulk_request_syncs_its_wal_once_and_a_reopen_replays_every_item(
        tmp_path, fsyncs):
    e = Engine(str(tmp_path))
    e.create_index("t", BODY, settings={"number_of_shards": 2})
    before = {k: metrics._counters.get(k, 0) for k in
              ("es.wal.syncs", "es.bulk.requests")}
    del fsyncs[:]
    res = e.bulk(_ops(0, 300))
    assert not res["errors"] and len(res["items"]) == 300
    assert len(fsyncs) == 1                       # not 300
    assert metrics._counters["es.wal.syncs"] - before["es.wal.syncs"] == 1
    assert metrics._counters["es.bulk.requests"] - before["es.bulk.requests"] == 1
    # what was acknowledged is on the data path now, without a close: a
    # second engine opened there replays every document
    again = Engine(str(tmp_path))
    try:
        docs = again.indices["t"].docs
        assert sorted(docs, key=int) == [str(i) for i in range(300)]
        assert all(d.alive and d.version == 1 for d in docs.values())
        assert docs["7"].source == {"body": "alpha w7 text 7"}
    finally:
        again.close()
        e.close()


def test_a_single_document_write_keeps_its_sync_an_operation(tmp_path, fsyncs):
    e = Engine(str(tmp_path))
    try:
        idx = e.create_index("t", BODY)
        del fsyncs[:]
        idx.index_doc("a", {"body": "one"})
        assert len(fsyncs) == 1
        idx.index_doc("a", {"body": "two"})       # an update
        assert len(fsyncs) == 2
        idx.delete_doc("a")
        assert len(fsyncs) == 3
    finally:
        e.close()


def test_an_item_that_fails_leaves_the_others_acknowledged_and_synced(
        tmp_path, fsyncs):
    e = Engine(str(tmp_path))
    e.create_index("t", BODY)
    e.create_index("u", BODY)
    ops = _ops(0, 5) + [("create", "t", "2", {"body": "a second 2"}),
                        ("delete", "t", "no such doc", None)] + _ops(5, 8, "u")
    del fsyncs[:]
    res = e.bulk(ops)
    assert res["errors"] is True
    status = [next(iter(it.values()))["status"] for it in res["items"]]
    assert status == [201] * 5 + [409, 404] + [201] * 3
    assert len(fsyncs) == 2                       # one a touched index
    again = Engine(str(tmp_path))
    try:
        assert sorted(again.indices["t"].docs) == ["0", "1", "2", "3", "4"]
        assert again.indices["t"].docs["2"].source == {"body": "alpha w2 text 2"}
        assert sorted(again.indices["u"].docs) == ["5", "6", "7"]
    finally:
        again.close()
        e.close()


def test_the_wal_record_is_the_line_json_dumps_gave(tmp_path):
    """`index_doc` writes its record around the source's own serialization;
    the line is what `json.dumps` of the whole record gave before."""
    import json

    e = Engine(str(tmp_path))
    try:
        idx = e.create_index("t", BODY)
        src = {"body": "café \"quoted\" \\ text", "n": 1.5, "k": [1, {"a": None}]}
        idx.index_doc('id "1"', src)
        idx.delete_doc('id "1"')
        with open(os.path.join(idx.data_dir, "translog.log")) as f:
            lines = f.read().splitlines()
        assert lines[0] == json.dumps(
            {"op": "index", "id": 'id "1"', "source": src, "version": 1,
             "seq_no": 0}, separators=(",", ":"))
        assert json.loads(lines[1]) == {"op": "delete", "id": 'id "1"',
                                        "version": 2, "seq_no": 1}
    finally:
        e.close()


# -- (b) the shards of a refresh, built at once -------------------------------

def _docs(n):
    rng = np.random.default_rng(7)
    return [(str(i), {"body": " ".join(f"w{t}" for t in rng.zipf(1.3, 12) % 900)})
            for i in range(n)]


def _arrays(obj, prefix=""):
    """Every ndarray reachable from a pack, by path."""
    out = {}
    if isinstance(obj, np.ndarray):
        out[prefix] = obj
    elif isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_arrays(v, f"{prefix}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(_arrays(v, f"{prefix}[{i}]"))
    elif hasattr(obj, "__dict__") and not isinstance(obj, Mappings):
        for k, v in vars(obj).items():
            if k not in ("mappings", "doc_sources", "stacked"):
                out.update(_arrays(v, f"{prefix}.{k}"))
    return out


def test_four_shards_built_at_once_are_the_serial_build_array_for_array(
        monkeypatch):
    monkeypatch.setenv("ES_TPU_DEVICE_BUILD_MIN", "0")   # the device stages too
    m = Mappings(BODY)
    docs = _docs(1200)
    serial = build_stacked_pack_routed(route_docs(docs, 4), m, shard_builders=1)
    at_once = build_stacked_pack_routed(route_docs(docs, 4), m, shard_builders=4)
    a, b = _arrays(serial), _arrays(at_once)
    assert set(a) == set(b) and len(a) > 30
    for path in a:
        assert a[path].dtype == b[path].dtype and np.array_equal(
            a[path], b[path], equal_nan=a[path].dtype.kind == "f"), path
    assert serial.global_df == at_once.global_df
    assert [p.term_dict for p in serial.shards] == [p.term_dict
                                                    for p in at_once.shards]


def test_each_shards_device_stage_runs_on_its_own_device(monkeypatch):
    monkeypatch.setenv("ES_TPU_DEVICE_BUILD_MIN", "0")
    mesh = make_mesh(4)
    assert mesh is not None and len(jax.devices()) >= 4
    placed = {"scatter": [], "impact": []}
    real_scatter = device_build._csr_scatter_jit()
    real_impact = device_build._impact_codes_jit()

    def scatter(*args, **kw):
        out = real_scatter(*args, **kw)          # four threads: one append each
        placed["scatter"].append({next(iter(a.devices())) for a in args[:5]}
                                 | {next(iter(o.devices())) for o in out})
        return out

    def impact(*args, **kw):
        out = real_impact(*args, **kw)
        if args[0].ndim == 2:       # a shard's build, not the searcher's [S, ...]
            placed["impact"].append({next(iter(a.devices())) for a in args}
                                    | set(out.devices()))
        return out

    monkeypatch.setattr(device_build, "_csr_scatter_jit", lambda: scatter)
    monkeypatch.setattr(device_build, "_impact_codes_jit", lambda: impact)
    e = Engine(None)
    try:
        e.create_index("t", BODY, settings={"number_of_shards": 4})
        assert not e.bulk(_ops(0, 400))["errors"]
        e.indices["t"].refresh()
        assert e.indices["t"]._searcher.mesh is not None
    finally:
        e.close()
    want = [{d} for d in mesh.devices]
    for stage in ("scatter", "impact"):
        got = placed[stage][-4:]                  # the refresh with documents
        assert sorted(got, key=lambda s: next(iter(s)).id) == want, stage
    spans = [s for root in TRACER.finished for s in _walk(root)
             if s.name == "refresh.shard_build"][-4:]
    assert [s.attributes["shard"] for s in spans] == [0, 1, 2, 3]
    assert [s.attributes["device"] for s in spans] == [str(d) for d in mesh.devices]


def _walk(span):
    yield span
    for c in span.children:
        yield from _walk(c)


def test_a_builder_that_throws_fails_the_refresh_and_the_old_searcher_stays(
        monkeypatch):
    e = Engine(None)
    try:
        e.create_index("t", BODY, settings={"number_of_shards": 4})
        idx = e.indices["t"]
        assert not e.bulk(_ops(0, 400))["errors"]
        idx.refresh()
        old = idx._searcher
        assert idx.search(query={"match": {"body": "alpha"}},
                          size=1)["hits"]["total"]["value"] == 400
        assert not e.bulk(_ops(400, 900))["errors"]   # too many for a tail
        real = PackBuilder.build
        built = []

        def build(self, *a, **kw):
            built.append(self)
            if len(built) == 3:
                raise RuntimeError("shard builder exploded")
            return real(self, *a, **kw)

        monkeypatch.setattr(PackBuilder, "build", build)
        with pytest.raises(RuntimeError, match="shard builder exploded"):
            idx.refresh()
        assert idx._searcher is old
        assert idx.search(query={"match": {"body": "alpha"}},
                          size=1)["hits"]["total"]["value"] == 400
        monkeypatch.setattr(PackBuilder, "build", real)
        idx.refresh()                                 # and the next one lands
        assert idx.search(query={"match": {"body": "alpha"}},
                          size=1)["hits"]["total"]["value"] == 900
    finally:
        e.close()


def test_the_counters_say_how_many_shards_were_built_at_once():
    m = Mappings(BODY)
    docs = _docs(400)

    def added(builders):
        keys = ("es.refresh.shard_build.ns", "es.refresh.build_wall.ns")
        before = [metrics._counters.get(k, 0) for k in keys]
        build_stacked_pack_routed(route_docs(docs, 4), m, shard_builders=builders)
        return [metrics._counters[k] - b for k, b in zip(keys, before)]

    shards, wall = added(1)
    assert 0 < shards <= wall                  # one after another: at most 1
    shards, wall = added(4)
    assert shards > 0 and wall > 0
    assert default_shard_builders(4) == min(4, os.cpu_count())
    assert default_shard_builders(1) == 1


# -- (c) the setting -----------------------------------------------------------

def test_the_setting_is_registered_dynamic_and_at_least_one(tmp_path):
    e = Engine(str(tmp_path))
    try:
        setting = e.settings.registry[SETTING]
        assert setting.dynamic and setting.default is None
        assert e.settings.get(SETTING) is None     # unset: min(shards, cores)
        e.settings.update({"persistent": {SETTING: 4}})
        assert e.settings.get(SETTING) == 4
        e.settings.update({"persistent": {SETTING: "2"}})
        assert e.settings.get(SETTING) == 2
        for bad in (0, -1, "many"):
            with pytest.raises(IllegalArgumentError):
                e.settings.update({"persistent": {SETTING: bad}})
        assert e.settings.get(SETTING) == 2
        idx = e.create_index("t", BODY, settings={"number_of_shards": 4})
        args = idx._shard_build_args(make_mesh(4))
        assert args["shard_builders"] == 2
        assert args["devices"] == list(jax.devices()[:4])
        assert idx._shard_build_args(None) == {"shard_builders": 2,
                                               "devices": None}
        e.settings.update({"persistent": {SETTING: None}})
        assert e.settings.get(SETTING) is None
    finally:
        e.close()


def test_a_server_without_the_setting_refuses_it_as_not_recognized():
    """What the parent's program answers the four-shard configuration: the
    registry with the entry taken out refuses the whole update, 400."""
    registry = [s for s in default_cluster_settings() if s.key != SETTING]
    assert len(registry) == len(default_cluster_settings()) - 1
    older = ClusterSettings(registry)
    with pytest.raises(IllegalArgumentError, match="not recognized") as err:
        older.update({"persistent": {"indices.requests.cache.enable": False,
                                     SETTING: 4}})
    assert err.value.status == 400 and SETTING in str(err.value)
    assert older.persistent == {}                  # nothing of it applied


# -- the analysis route: a burst in one call of the C accumulator ---------------

def _packed(builder):
    keys, post_off, docs, tfs, pos_off, pos = builder._native.pack()
    return (keys, post_off.tolist(), docs.tolist(), tfs.tolist(),
            pos_off.tolist(), pos.tolist(), dict(builder.doc_field_lengths))


def test_a_burst_in_one_native_call_leaves_the_state_of_a_call_a_document():
    from elasticsearch_tpu.native import available

    if not available():
        pytest.skip("no native accumulator here")
    m = Mappings(BODY)
    analyzer = m.fields["body"].get_batched_analyzer().analyzer
    fdocs = [3, 4, 7, 9]
    vals = ["The quick brown fox's den", "jumps over", "", "a a A b",
            "x" * 300 + " tail", "last one", "and a second value of it"]
    vdoc = [0, 0, 1, 1, 2, 3, 3]            # values of one document adjacent

    one_call = PackBuilder(m)
    assert one_call._add_texts_native("body", fdocs, vals, vdoc, analyzer)
    by_doc = PackBuilder(m)
    for d_ord, docid in enumerate(fdocs):
        by_doc._add_text_native(
            "body", docid, analyzer,
            [v for v, d in zip(vals, vdoc) if d == d_ord])
    assert _packed(one_call) == _packed(by_doc)
    assert one_call.doc_field_lengths["body"] == [(3, 7), (4, 4), (7, 3), (9, 8)]

    # a value that is not ASCII: nothing is added, the caller goes by document
    other = PackBuilder(m)
    assert not other._add_texts_native("body", [0, 1], ["plain", "café"],
                                       [0, 1], analyzer)
    assert other.doc_field_lengths == {} and other._native.pack()[0] == []
    # nor with another analyzer than the plain standard one
    stop = Mappings({"properties": {"body": {"type": "text",
                                             "analyzer": "stop"}}})
    assert not PackBuilder(stop)._add_texts_native(
        "body", [0], ["plain"], [0],
        stop.fields["body"].get_batched_analyzer().analyzer)


def test_routing_in_one_native_call_is_shard_for_id_of_every_id():
    import random
    import string

    from elasticsearch_tpu.cluster.routing import shard_for_id
    from elasticsearch_tpu.native import available
    from elasticsearch_tpu.parallel.stacked import _shards_for_ids

    if not available():
        pytest.skip("no native library here")
    rng = random.Random(3)
    ids = ([str(i) for i in range(3000)] + ["", "a", "ab", "abc", "abcd"]
           + ["".join(rng.choices(string.printable[:94], k=rng.randint(0, 23)))
              for _ in range(2000)])
    for shards in (1, 2, 3, 4, 7, 30, 1024):
        assert _shards_for_ids(ids, shards) == [shard_for_id(i, shards)
                                                for i in ids], shards
    assert _shards_for_ids(["plain", "café"], 4) is None   # id by id then
    docs = [(i, {"n": n}) for n, i in enumerate(ids)]
    routed = route_docs(docs, 4)
    assert sorted((d for lst in routed for d in lst),
                  key=lambda d: d[1]["n"]) == docs
    assert all(shard_for_id(i, 4) == s for s, lst in enumerate(routed)
               for i, _ in lst)
    mixed = route_docs(docs + [("café", {})], 4)            # the Python way
    assert [lst[:len(r)] for lst, r in zip(mixed, routed)] == routed
