"""REST API layer (aiohttp): the Elasticsearch HTTP contract.

Endpoint shapes follow the reference's API specs (reference:
rest-api-spec/src/main/resources/rest-api-spec/api/*.json — search.json,
bulk.json, index.json, indices.create.json, count.json, msearch.json, … —
and handler routing in rest/RestController.java:326). Engine work runs on a
single-thread executor so the event loop stays responsive and engine state
is accessed serially (the write path of the reference is likewise
single-writer per shard via operation permits, index/shard/IndexShard.java).

Error envelope parity: {"error": {"type", "reason", ...}, "status": N}
(reference behavior: ElasticsearchException REST rendering).
"""

from __future__ import annotations

import asyncio
import json
import time
from concurrent.futures import ThreadPoolExecutor

from aiohttp import web

from .. import __version__
from ..engine import Engine
from ..telemetry import TRACER
from ..utils.errors import ElasticsearchTpuError, IllegalArgumentError

JSON = "application/json"


def _track_total_hits_param(body, query_params):
    v = body.get("track_total_hits")
    if v is None:
        raw = query_params.get("track_total_hits")
        if raw is None:
            return None
        v = True if raw in ("", "true") else False if raw == "false" else raw
    if isinstance(v, bool):
        return v
    try:
        return int(v)
    except (TypeError, ValueError):
        raise IllegalArgumentError(
            f"[track_total_hits] must be a boolean or an integer, got [{v}]")


def _bool_param(query_params, name, default=False):
    v = query_params.get(name)
    if v is None:
        return default
    return v in ("", "true", "1")


def _err_response(ex: Exception) -> web.Response:
    if isinstance(ex, ElasticsearchTpuError):
        body = ex.to_dict()
        status = ex.status
    else:
        body = {"error": {"type": "exception", "reason": str(ex)}, "status": 500}
        status = 500
    headers = None
    # load-shed errors carry a backoff hint (serving admission, breaker
    # trips surfaced through it): 429 + Retry-After, the reference's
    # EsRejectedExecutionException discipline clients already understand
    retry_after = getattr(ex, "retry_after_s", None)
    if retry_after is not None:
        headers = {"Retry-After": str(int(max(1, retry_after)))}
    return web.json_response(body, status=status, headers=headers)


@web.middleware
async def _tracing_middleware(request: web.Request, handler):
    """Distributed tracing at the REST boundary: accept a W3C
    `traceparent` (+ `X-Opaque-Id` task identity) or mint a fresh trace,
    run the request under a root span, and hand the trace id back in the
    response headers — the reference's RestController + ThreadContext
    trace-header behavior, with the APM agent replaced by the in-process
    tracer (telemetry.TRACER)."""
    import time as _time

    from ..telemetry import (TraceContext, activate_trace,
                             format_traceparent, metrics, new_trace_id,
                             parse_traceparent)

    parsed = parse_traceparent(request.headers.get("traceparent"))
    ctx = TraceContext(
        trace_id=parsed[0] if parsed else new_trace_id(),
        parent_span_id=parsed[1] if parsed else None,
        task_id=request.headers.get("X-Opaque-Id"),
    )
    node = request.app["engine"].tasks.node
    # named by the route's template, not by the literal path: a name per
    # document id would be a name per document wherever names are summed
    resource = request.match_info.route.resource
    route = resource.canonical if resource is not None else "<unmatched>"
    t0 = _time.perf_counter()
    with activate_trace(ctx, node=node):
        with TRACER.span(f"http {request.method} {route}",
                         method=request.method, path=request.path,
                         **({"task_id": ctx.task_id} if ctx.task_id else {})
                         ) as span:
            resp = await handler(request)
            span.attributes["status"] = resp.status
    ms = (_time.perf_counter() - t0) * 1000
    metrics.histogram_record("es.rest.request.ms", ms)
    resp.headers["X-Trace-Id"] = ctx.trace_id
    resp.headers["traceparent"] = format_traceparent(ctx.trace_id,
                                                     span.span_id)
    return resp


@web.middleware
async def _warnings_middleware(request: web.Request, handler):
    """Deprecation warnings emitted during the request become RFC-7234
    `Warning` response headers (HeaderWarning analog)."""
    from ..telemetry import begin_request_warnings, drain_request_warnings, warning_header_value

    begin_request_warnings()
    resp = await handler(request)
    for msg in drain_request_warnings():
        resp.headers.add("Warning", warning_header_value(msg))
    return resp


@web.middleware
async def _xcontent_middleware(request: web.Request, handler):
    """Response content negotiation: Accept: application/yaml|cbor (or
    ?format=) re-encodes the JSON payload in the requested x-content
    format (XContentType negotiation; SMILE is a documented divergence)."""
    resp = await handler(request)
    want = (request.query.get("format") or "").lower()
    if not want:
        accept = (request.headers.get("Accept") or "").split(";")[0].strip().lower()
        want = {"application/yaml": "yaml", "text/yaml": "yaml",
                "application/cbor": "cbor"}.get(accept, "")
    if want in ("yaml", "cbor") and resp.content_type == "application/json" \
            and getattr(resp, "body", None):
        from ..utils.xcontent import dumps as xdumps

        payload, ctype = xdumps(json.loads(resp.body), want)
        return web.Response(body=payload, status=resp.status,
                            content_type=ctype, headers={
                                k: v for k, v in resp.headers.items()
                                if k.lower() not in ("content-type",
                                                     "content-length")})
    return resp


@web.middleware
async def _security_middleware(request: web.Request, handler):
    engine = request.app["engine"]
    sec = engine.security
    if not sec.enabled:
        return await handler(request)
    from ..security import AuthenticationError, AuthorizationError
    from ..security.authz import classify

    try:
        principal = sec.authenticate(request.headers.get("Authorization"))
        action, indices = classify(request.method, request.path)
        if action != "authenticated":
            sec.authorize(principal, action, indices)
        request["principal"] = principal
    except (AuthenticationError, AuthorizationError) as ex:
        resp = _err_response(ex)
        if ex.status == 401:
            resp.headers["WWW-Authenticate"] = 'Basic realm="security"'
        return resp
    return await handler(request)


def make_app(engine: Engine | None = None, data_path: str | None = None) -> web.Application:
    engine = engine or Engine(data_path)
    app = web.Application(
        client_max_size=512 * 1024 * 1024,
        middlewares=[_tracing_middleware, _xcontent_middleware,
                     _warnings_middleware, _security_middleware],
    )
    app["engine"] = engine
    # single-thread executor: serializes engine mutation, keeps the loop free
    app["pool"] = ThreadPoolExecutor(max_workers=1, thread_name_prefix="engine")
    # the background monitoring tick serializes its engine access through
    # the same worker instead of racing REST traffic (monitoring/service)
    engine.monitoring.submit = app["pool"].submit
    # likewise the persistent-task ticker (scheduled watches, ML realtime,
    # CCR follows): each pass runs on the engine worker; watcher exports
    # flush on the ticker thread afterwards (tasks/persistent)
    engine.persistent.submit = app["pool"].submit
    # serving waves run their engine-touching stages on the same worker
    # (one engine thread, searches and mutations serialized), while the
    # completer thread pulls device outputs off-thread
    engine.serving.bind_executor(app["pool"].submit)
    from ..monitoring import install_compile_listener

    install_compile_listener()

    async def call(fn, *args, **kwargs):
        loop = asyncio.get_running_loop()
        # carry the request's contextvars (trace context, active span,
        # profile collector) onto the engine worker thread, so spans and
        # profiling events recorded there belong to THIS request
        import contextvars

        ctx = contextvars.copy_context()
        cur = TRACER.current_span()
        handed = time.perf_counter_ns()

        def on_engine_thread():
            if cur is not None and cur.name == "rest.search":
                # a search's wait behind the one engine thread, which no
                # thread performs; any other endpoint's is in its root alone
                TRACER.record("engine.queue", handed, time.perf_counter_ns())
            return fn(*args, **kwargs)

        return await loop.run_in_executor(
            app["pool"], ctx.run, on_engine_thread)

    def engine_search(fn, *args, **kwargs):
        """Everything the engine thread does for one search, as one span."""
        with TRACER.span("engine.search"):
            return fn(*args, **kwargs)

    def handler(fn):
        async def wrapped(request: web.Request):
            try:
                return await fn(request)
            except ElasticsearchTpuError as ex:
                return _err_response(ex)
            except json.JSONDecodeError as ex:
                return _err_response(IllegalArgumentError(f"failed to parse request body: {ex}"))
            except Exception as ex:  # noqa: BLE001 - error envelope boundary
                return _err_response(ex)

        return wrapped

    async def body_json(request, default=None):
        raw = await request.read()
        if not raw:
            return default
        from ..utils.xcontent import loads as xloads

        return xloads(raw, request.headers.get("Content-Type"))

    # ---- root / info -----------------------------------------------------

    @handler
    async def root(request):
        return web.json_response(
            {
                "name": "elasticsearch-tpu",
                "cluster_name": "elasticsearch-tpu",
                "version": {
                    "number": "8.14.0",
                    "build_flavor": "tpu",
                    "framework_version": __version__,
                    "lucene_version": "none (blocked-CSR HBM packs)",
                },
                "tagline": "You Know, for Search (on TPUs)",
            }
        )

    # ---- index management ------------------------------------------------

    @handler
    async def create_index(request):
        name = request.match_info["index"]
        body = await body_json(request, {}) or {}
        mappings = body.get("mappings")
        settings = body.get("settings", {})
        if "index" in settings:
            settings = {**settings, **settings.pop("index")}
        await call(engine.create_index, name, mappings, settings, body.get("aliases"))
        return web.json_response({"acknowledged": True, "shards_acknowledged": True, "index": name})

    @handler
    async def delete_index(request):
        await call(engine.delete_index, request.match_info["index"])
        return web.json_response({"acknowledged": True})

    @handler
    async def get_index(request):
        idx = _concrete(request.match_info["index"])
        return web.json_response(
            {
                idx.name: {
                    "aliases": engine.meta.aliases_of(idx.name),
                    "mappings": idx.mappings.to_dict(),
                    "settings": {"index": {k: str(v) for k, v in idx.settings.items()}},
                }
            }
        )

    @handler
    async def head_index(request):
        if request.match_info["index"] in engine.indices:
            return web.Response(status=200)
        return web.Response(status=404)

    @handler
    async def get_mapping(request):
        idx = _concrete(request.match_info["index"])
        return web.json_response({idx.name: {"mappings": idx.mappings.to_dict()}})

    @handler
    async def put_mapping(request):
        idx = _concrete(request.match_info["index"])
        body = await body_json(request, {}) or {}
        await call(idx.mappings.merge, body)
        idx._persist_meta()
        return web.json_response({"acknowledged": True})

    @handler
    async def refresh_index(request):
        """`_shards` derives from the actual per-index outcome (PR 14) —
        a thrown refresh becomes a failures[] entry instead of the
        unconditional `failed: 0` this block used to hardcode."""
        name = request.match_info.get("index")
        targets = (
            [i for i, _ in engine.resolve_search(name)]
            if name
            else list(engine.indices.values())
        )
        failures = []
        for idx in targets:
            try:
                await call(idx.refresh)
            except Exception as ex:  # noqa: BLE001 - per-shard envelope
                failures.append({
                    "shard": 0, "index": idx.name,
                    "node": engine.tasks.node,
                    "reason": {"type": type(ex).__name__.lower(),
                               "reason": str(ex)[:512]}})
        n = len(targets)
        shards = {"total": n, "successful": n - len(failures),
                  "failed": len(failures)}
        if failures:
            shards["failures"] = failures
        # broadcast-op semantics (reference: BroadcastResponse): 200 with
        # the failure list — partial success is not an HTTP error
        return web.json_response({"_shards": shards})

    @handler
    async def flush_index(request):
        idx = _concrete(request.match_info["index"])
        try:
            await call(idx.flush)
        except Exception as ex:  # noqa: BLE001 - honest _shards envelope
            return web.json_response({"_shards": {
                "total": 1, "successful": 0, "failed": 1,
                "failures": [{"shard": 0, "index": idx.name,
                              "node": engine.tasks.node,
                              "reason": {"type": type(ex).__name__.lower(),
                                         "reason": str(ex)[:512]}}]}})
        return web.json_response({"_shards": {"total": 1, "successful": 1, "failed": 0}})

    # ---- documents -------------------------------------------------------

    def _concrete(name):
        return engine.get_index(engine.resolve_write_index(name))


    def _doc_result(r, index_name, request=None):
        out = {
            "_index": index_name,
            "_id": r["_id"],
            "_version": r["_version"],
            "_seq_no": r["_seq_no"],
            "_primary_term": 1,
            "result": r["result"],
            "_shards": {"total": 1, "successful": 1, "failed": 0},
        }
        if request is not None:
            refresh = request.query.get("refresh")
            # forced_refresh: true when the write itself forced a refresh
            # (refresh=true or the bare param); wait_for reports false
            # (reference behavior: DocWriteResponse.forcedRefresh)
            if refresh in ("", "true"):
                out["forced_refresh"] = True
            if request.query.get("routing"):
                out["_routing"] = request.query["routing"]
        return out

    async def _maybe_pipeline(idx, body, request, doc_id):
        """Apply request/default/final ingest pipelines to a single-doc
        write; returns None when a drop processor fired."""
        pipeline = request.query.get("pipeline")
        first, final = engine.resolve_pipelines(idx, pipeline)
        if first or final:
            return await call(engine.run_pipelines_resolved, idx.name, body,
                              first, final, doc_id)
        return body

    @handler
    async def put_doc(request):
        name = request.match_info["index"]
        doc_id = request.match_info.get("id")
        body = await body_json(request)
        if not isinstance(body, dict):
            raise IllegalArgumentError("request body is required")
        op_type = request.query.get("op_type", "index")
        idx = await call(engine.get_or_autocreate, name)
        if request.query.get("routing") and idx.ts_mode is not None:
            raise IllegalArgumentError(
                f"specifying routing is not supported because the "
                f"destination index [{idx.name}] is in time series mode")
        body = await _maybe_pipeline(idx, body, request, doc_id)
        if body is None:  # drop processor fired
            return web.json_response(
                {"_index": name, "_id": doc_id, "result": "noop"})
        r = await call(idx.index_doc, doc_id, body, op_type)
        if request.query.get("refresh") in ("", "true", "wait_for"):
            await call(idx.refresh)
        status = 201 if r["result"] == "created" else 200
        return web.json_response(_doc_result(r, name, request), status=status)

    @handler
    async def create_doc(request):
        name = request.match_info["index"]
        doc_id = request.match_info["id"]
        body = await body_json(request)
        if not isinstance(body, dict):
            raise IllegalArgumentError("request body is required")
        idx = await call(engine.get_or_autocreate, name)
        body = await _maybe_pipeline(idx, body, request, doc_id)
        if body is None:  # drop processor fired
            return web.json_response(
                {"_index": name, "_id": doc_id, "result": "noop"})
        r = await call(idx.index_doc, doc_id, body, "create")
        if request.query.get("refresh") in ("", "true", "wait_for"):
            await call(idx.refresh)
        return web.json_response(_doc_result(r, name, request), status=201)

    @handler
    async def get_doc(request):
        idx = _concrete(request.match_info["index"])
        got = idx.get_doc(request.match_info["id"])
        if got is None:
            return web.json_response(
                {"_index": idx.name, "_id": request.match_info["id"], "found": False},
                status=404,
            )
        return web.json_response({"_index": idx.name, "found": True, **got})

    @handler
    async def head_doc(request):
        idx = _concrete(request.match_info["index"])
        return web.Response(status=200 if idx.get_doc(request.match_info["id"]) else 404)

    @handler
    async def get_source(request):
        idx = _concrete(request.match_info["index"])
        got = idx.get_doc(request.match_info["id"])
        if got is None:
            return web.json_response(
                {"error": {"type": "resource_not_found_exception"}, "status": 404}, status=404
            )
        return web.json_response(got["_source"])

    @handler
    async def delete_doc(request):
        idx = _concrete(request.match_info["index"])
        r = await call(idx.delete_doc, request.match_info["id"])
        if request.query.get("refresh") in ("", "true", "wait_for"):
            await call(idx.refresh)
        return web.json_response({**_doc_result(r, idx.name, request), "result": "deleted"})

    @handler
    async def update_doc(request):
        name = request.match_info["index"]
        body = await body_json(request, {}) or {}
        r = await call(
            engine.update_doc_api, name, request.match_info["id"], body
        )
        if request.query.get("refresh") in ("", "true", "wait_for"):
            await call(_concrete(name).refresh)
        status = 201 if r["result"] == "created" else 200
        return web.json_response(_doc_result(r, engine.resolve_write_index(name), request),
                                 status=status)

    async def run_task(request, action, description, fn):
        """Run `fn(task)` under a registered task. wait_for_completion=false
        detaches: the result lands in the task results store (the analog of
        the reference's `.tasks` results index) and {"task": id} returns
        immediately (reference behavior: rest-api-spec update_by_query.json /
        reindex.json wait_for_completion param)."""
        tm = engine.tasks
        task = tm.register(action, description)
        if _bool_param(request.query, "wait_for_completion", True):
            try:
                return web.json_response(await call(fn, task))
            finally:
                tm.unregister(task)
        tm.store_placeholder(task)

        def bg():
            try:
                tm.store_result(task, response=fn(task))
            except ElasticsearchTpuError as ex:
                tm.store_result(task, error=ex.to_dict()["error"])
            except Exception as ex:  # noqa: BLE001
                tm.store_result(task, error={"type": "exception", "reason": str(ex)})
            finally:
                tm.unregister(task)

        app["pool"].submit(bg)
        return web.json_response({"task": task.task_id})

    @handler
    async def update_by_query(request):
        body = await body_json(request, {}) or {}
        index = request.match_info["index"]
        return await run_task(
            request, "indices:data/write/update/byquery",
            f"update-by-query [{index}]",
            lambda task: engine.update_by_query(
                index,
                query=body.get("query"), script=body.get("script"),
                max_docs=body.get("max_docs"),
                refresh=_bool_param(request.query, "refresh"),
                pipeline=request.query.get("pipeline"),
                task=task,
            ),
        )

    @handler
    async def delete_by_query(request):
        body = await body_json(request, {}) or {}
        if "query" not in body:
            raise IllegalArgumentError("query is missing")
        index = request.match_info["index"]
        return await run_task(
            request, "indices:data/write/delete/byquery",
            f"delete-by-query [{index}]",
            lambda task: engine.delete_by_query(
                index,
                query=body.get("query"), max_docs=body.get("max_docs"),
                refresh=_bool_param(request.query, "refresh"),
                task=task,
            ),
        )

    @handler
    async def reindex(request):
        body = await body_json(request, {}) or {}
        return await run_task(
            request, "indices:data/write/reindex", "reindex",
            lambda task: engine.reindex(body, task=task),
        )

    # ---- search templates / stored scripts -------------------------------

    @handler
    async def search_template(request):
        from ..search.templates import resolve_template

        body = await body_json(request, {}) or {}
        _, parsed = resolve_template(engine.meta, body)
        return web.json_response(
            await _run_search(request.match_info.get("index"), parsed, request.query)
        )

    @handler
    async def render_search_template(request):
        from ..search.templates import resolve_template

        body = await body_json(request, {}) or {}
        tid = request.match_info.get("id")
        if tid:
            body = {**body, "id": tid}
        _, parsed = resolve_template(engine.meta, body)
        return web.json_response({"template_output": parsed})

    @handler
    async def put_stored_script(request):
        body = await body_json(request, {}) or {}
        script = body.get("script")
        if not isinstance(script, dict) or "source" not in script:
            raise IllegalArgumentError("stored script requires [script.source]")
        engine.meta.stored_scripts[request.match_info["id"]] = {
            "lang": script.get("lang", "mustache"),
            "source": script["source"],
        }
        engine.meta.save()
        return web.json_response({"acknowledged": True})

    @handler
    async def get_stored_script(request):
        sid = request.match_info["id"]
        script = engine.meta.stored_scripts.get(sid)
        if script is None:
            return web.json_response({"_id": sid, "found": False}, status=404)
        return web.json_response({"_id": sid, "found": True, "script": script})

    @handler
    async def delete_stored_script(request):
        sid = request.match_info["id"]
        if sid not in engine.meta.stored_scripts:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"stored script [{sid}] not found")
        del engine.meta.stored_scripts[sid]
        engine.meta.save()
        return web.json_response({"acknowledged": True})

    # ---- admin / observability -------------------------------------------

    @handler
    async def knn_search_api(request):
        """Deprecated 8.x _knn_search endpoint (knn now lives in _search)."""
        from ..telemetry import add_deprecation_warning

        add_deprecation_warning(
            "The kNN search API has been replaced by the `knn` option in the "
            "search API.")
        body = await body_json(request, {}) or {}
        knn = body.get("knn")
        if not isinstance(knn, dict):
            raise IllegalArgumentError("[knn] object is required")
        # top-level filter/num_candidates ride along into the knn search
        # option (the deprecated API kept them OUTSIDE the knn object —
        # dropping them silently changed results)
        knn = dict(knn)
        if body.get("filter") is not None and knn.get("filter") is None:
            knn["filter"] = body["filter"]
        if (body.get("num_candidates") is not None
                and knn.get("num_candidates") is None):
            knn["num_candidates"] = body["num_candidates"]
        return web.json_response(await _run_search(
            request.match_info["index"],
            {"knn": knn, "size": knn.get("k", 10),
             "_source": body.get("_source"), "fields": body.get("fields")},
            request.query))

    # ---- graph / synonyms / recovery -------------------------------------

    @handler
    async def graph_explore(request):
        from ..xpack.graph import explore

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            explore, engine, request.match_info["index"], body))

    @handler
    async def put_synonyms(request):
        """PUT /_synonyms/{set}: named synonym sets usable by synonym token
        filters via "synonyms_set" (reference behavior: synonyms API +
        ReloadableCustomAnalyzer — here analyzers resolve sets lazily)."""
        body = await body_json(request, {}) or {}
        rules = body.get("synonyms_set")
        if not isinstance(rules, list):
            raise IllegalArgumentError("[synonyms_set] list is required")
        set_name = request.match_info["set"]
        resolved = [r["synonyms"] if isinstance(r, dict) else str(r)
                    for r in rules]
        created = set_name not in engine.meta.extras.get("synonym_sets", {})
        engine.meta.extras.setdefault("synonym_sets", {})[set_name] = resolved

        def reload_analyzers():
            # push the new rules into every index whose analysis references
            # the set (the reload-search-analyzers analog; documents indexed
            # under the old rules keep them until reindex, as in ES)
            from ..analysis.custom import build_analysis_registry

            for idx in engine.indices.values():
                analysis = idx.settings.get("analysis") or {}
                touched = False
                for fspec in (analysis.get("filter") or {}).values():
                    if isinstance(fspec, dict) and fspec.get("synonyms_set") == set_name:
                        fspec["_resolved_set"] = list(resolved)
                        touched = True
                if touched:
                    idx.mappings.set_analysis(build_analysis_registry(analysis))
                    idx._persist_meta()

        await call(reload_analyzers)
        engine.meta.save()
        return web.json_response({"result": "created" if created else "updated"})

    @handler
    async def get_synonyms(request):
        sets = engine.meta.extras.get("synonym_sets", {})
        name = request.match_info.get("set")
        if name:
            if name not in sets:
                from ..utils.errors import ResourceNotFoundError

                raise ResourceNotFoundError(f"synonym set [{name}] not found")
            return web.json_response({
                "count": len(sets[name]),
                "synonyms_set": [{"id": str(i), "synonyms": r}
                                 for i, r in enumerate(sets[name])],
            })
        return web.json_response({"count": len(sets), "results": [
            {"synonyms_set": n, "count": len(r)} for n, r in sorted(sets.items())
        ]})

    @handler
    async def delete_synonyms(request):
        sets = engine.meta.extras.get("synonym_sets", {})
        name = request.match_info["set"]
        if name not in sets:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"synonym set [{name}] not found")
        del sets[name]
        engine.meta.save()
        return web.json_response({"acknowledged": True})

    @handler
    async def index_recovery(request):
        from ..engine import admin

        out = {}
        for idx, _ in engine.resolve_search(
                request.match_info.get("index") or "_all", allow_no_indices=True):
            out[idx.name] = {"shards": [
                {"id": sh, "type": "EMPTY_STORE", "stage": "DONE",
                 "primary": True,
                 "source": {}, "target": {"name": engine.tasks.node},
                 "index": {"size": {"total_in_bytes":
                                    admin._index_store_bytes(idx)},
                           "files": {"percent": "100.0%"}}}
                for sh in range(idx.num_shards)
            ]}
        return web.json_response(out)

    # ---- legacy index templates (deprecated API) -------------------------

    _LEGACY_TPL_WARNING = (
        "Legacy index templates are deprecated in favor of composable "
        "templates."
    )

    @handler
    async def legacy_put_template(request):
        from ..telemetry import add_deprecation_warning

        add_deprecation_warning(_LEGACY_TPL_WARNING)
        body = await body_json(request, {}) or {}
        name = request.match_info["name"]
        existing = engine.meta.index_templates.get(name)
        if existing is not None and not existing.get("_legacy"):
            raise IllegalArgumentError(
                f"a composable index template [{name}] already exists; "
                "legacy and composable templates cannot share a name"
            )
        tpl = {
            "index_patterns": body.get("index_patterns") or [],
            "priority": int(body.get("order", 0)),
            "template": {
                "settings": body.get("settings") or {},
                "mappings": body.get("mappings") or {},
                "aliases": body.get("aliases") or {},
            },
            "_legacy": True,
        }
        engine.meta.index_templates[name] = tpl
        engine.meta.save()
        return web.json_response({"acknowledged": True})

    @handler
    async def legacy_get_template(request):
        from ..telemetry import add_deprecation_warning

        add_deprecation_warning(_LEGACY_TPL_WARNING)
        name = request.match_info.get("name")
        out = {}
        for n, t in engine.meta.index_templates.items():
            if not t.get("_legacy"):
                continue
            if name and n != name:
                continue
            body = t.get("template") or {}
            out[n] = {"index_patterns": t.get("index_patterns", []),
                      "order": t.get("priority", 0),
                      "settings": body.get("settings", {}),
                      "mappings": body.get("mappings", {}),
                      "aliases": body.get("aliases", {})}
        if name and not out:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"index_template [{name}] missing")
        return web.json_response(out)

    @handler
    async def legacy_delete_template(request):
        from ..telemetry import add_deprecation_warning

        add_deprecation_warning(_LEGACY_TPL_WARNING)
        name = request.match_info["name"]
        t = engine.meta.index_templates.get(name)
        if t is None or not t.get("_legacy"):
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"index_template [{name}] missing")
        del engine.meta.index_templates[name]
        engine.meta.save()
        return web.json_response({"acknowledged": True})

    # ---- index state / resize --------------------------------------------

    @handler
    async def close_index_api(request):
        return web.json_response(await call(
            engine.close_index, request.match_info["index"]))

    @handler
    async def open_index_api(request):
        return web.json_response(await call(
            engine.open_index, request.match_info["index"]))

    @handler
    async def add_block_api(request):
        return web.json_response(await call(
            engine.add_block, request.match_info["index"],
            request.match_info["block"]))

    @handler
    async def clone_index_api(request):
        return web.json_response(await call(
            engine.clone_index, request.match_info["index"],
            request.match_info["target"]))

    @handler
    async def msearch_template(request):
        from ..search.templates import resolve_template

        raw = (await request.read()).decode("utf-8")
        lines = [ln for ln in raw.split("\n") if ln.strip()]
        responses = []
        for i in range(0, len(lines) - 1, 2):
            header = json.loads(lines[i])
            tpl = json.loads(lines[i + 1])
            try:
                _, parsed = resolve_template(engine.meta, tpl)
                res = await _run_search(
                    header.get("index") or request.match_info.get("index"),
                    parsed, {})
                responses.append({**res, "status": 200})
            except ElasticsearchTpuError as ex:
                responses.append({**ex.to_dict(), "status": ex.status})
        return web.json_response({"took": 1, "responses": responses})

    @handler
    async def mtermvectors(request):
        from ..engine import admin

        body = await body_json(request, {}) or {}
        default_index = request.match_info.get("index")
        docs = body.get("docs")
        if docs is None and body.get("ids"):
            docs = [{"_id": i} for i in body["ids"]]
        out = []
        for d in docs or []:
            index_name = d.get("_index", default_index)
            doc_id = d.get("_id")
            if not index_name or doc_id is None:
                out.append({"_index": index_name, "_id": doc_id,
                            "error": {"type": "illegal_argument_exception",
                                      "reason": "[_index] and [_id] are required"}})
                continue
            try:
                out.append(await call(
                    admin.termvectors, engine, index_name, doc_id, d, None))
            except ElasticsearchTpuError as ex:
                out.append({"_index": index_name, "_id": doc_id,
                            **ex.to_dict()})
        return web.json_response({"docs": out})

    @handler
    async def cluster_allocation_explain(request):
        return web.json_response({
            "note": "every shard is assigned on this node",
            "can_allocate": "yes",
            "allocate_explanation": "single-node engine: shards colocate with packs",
        })

    @handler
    async def cluster_pending_tasks(request):
        return web.json_response({"tasks": []})

    # ---- CCR / SLM / Watcher / Enrich / health ---------------------------

    def _xcall(mod_name, fn_name, *args):
        import importlib

        mod = importlib.import_module(f"elasticsearch_tpu.{mod_name}")
        return call(getattr(mod, fn_name), engine, *args)

    @handler
    async def ccr_changes(request):
        from .. import ccr as ccr_mod

        return web.json_response(await call(
            ccr_mod.changes, engine, request.match_info["index"],
            int(request.query.get("from_seq_no", 0)),
            int(request.query.get("size", 512)),
        ))

    @handler
    async def ccr_follow(request):
        from .. import ccr as ccr_mod

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            ccr_mod.follow, engine, request.match_info["index"], body))

    @handler
    async def ccr_pause(request):
        return web.json_response(await _xcall("ccr", "pause_follow",
                                              request.match_info["index"]))

    @handler
    async def ccr_resume(request):
        return web.json_response(await _xcall("ccr", "resume_follow",
                                              request.match_info["index"]))

    @handler
    async def ccr_unfollow(request):
        return web.json_response(await _xcall("ccr", "unfollow",
                                              request.match_info["index"]))

    @handler
    async def ccr_stats_api(request):
        return web.json_response(await _xcall("ccr", "ccr_stats"))

    @handler
    async def slm_put(request):
        body = await body_json(request, {}) or {}
        return web.json_response(await _xcall(
            "xpack", "slm_put_policy", request.match_info["id"], body))

    @handler
    async def slm_get(request):
        return web.json_response(await _xcall(
            "xpack", "slm_get_policy", request.match_info.get("id")))

    @handler
    async def slm_delete(request):
        return web.json_response(await _xcall(
            "xpack", "slm_delete_policy", request.match_info["id"]))

    @handler
    async def slm_execute_api(request):
        return web.json_response(await _xcall(
            "xpack", "slm_execute", request.match_info["id"]))

    @handler
    async def watcher_put_api(request):
        from ..xpack import watcher_ensure_executor

        body = await body_json(request, {}) or {}
        res = await _xcall("xpack", "watcher_put", request.match_info["id"], body)
        await call(watcher_ensure_executor, engine)
        return web.json_response(res)

    @handler
    async def watcher_get_api(request):
        return web.json_response(await _xcall(
            "xpack", "watcher_get", request.match_info["id"]))

    @handler
    async def watcher_delete_api(request):
        return web.json_response(await _xcall(
            "xpack", "watcher_delete", request.match_info["id"]))

    @handler
    async def watcher_execute_api(request):
        return web.json_response(await _xcall(
            "xpack", "watcher_execute", request.match_info["id"]))

    @handler
    async def watcher_ack_api(request):
        return web.json_response(await call(
            engine.watcher.ack, request.match_info["id"],
            request.match_info.get("action_id")))

    @handler
    async def watcher_activate_api(request):
        return web.json_response(await call(
            engine.watcher.activate, request.match_info["id"], True))

    @handler
    async def watcher_deactivate_api(request):
        return web.json_response(await call(
            engine.watcher.activate, request.match_info["id"], False))

    @handler
    async def watcher_stats_api(request):
        st = await call(engine.watcher.stats)
        return web.json_response({
            "_nodes": {"total": 1, "successful": 1, "failed": 0},
            "cluster_name": "elasticsearch-tpu",
            "manually_stopped": not engine.watcher.enabled,
            "stats": [{"node_id": engine.tasks.node, **st}],
        })

    @handler
    async def watcher_start_api(request):
        from ..xpack.watcher import ensure_executor

        await call(ensure_executor, engine)
        return web.json_response({"acknowledged": True})

    @handler
    async def watcher_stop_api(request):
        # default executor, NOT the engine worker: stop joins the ticker
        # thread, which may itself be waiting on a tick it submitted to
        # the worker — joining from the worker would stall both
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, engine.persistent.stop_ticker)
        return web.json_response({"acknowledged": True})

    @handler
    async def slo_api(request):
        """GET /_slo: the registered objectives and their latest
        evaluation (?evaluate=true forces a fresh pass — reads otherwise
        serve the monitoring-interval cached evaluation)."""
        force = request.query.get("evaluate") in ("", "true", "1")
        ev = await call(
            engine.slo.evaluate if force else engine.slo.current)
        return web.json_response({"slo": ev})

    @handler
    async def enrich_put(request):
        body = await body_json(request, {}) or {}
        return web.json_response(await _xcall(
            "xpack", "enrich_put_policy", request.match_info["name"], body))

    @handler
    async def enrich_execute(request):
        return web.json_response(await _xcall(
            "xpack", "enrich_execute_policy", request.match_info["name"]))

    @handler
    async def enrich_get(request):
        return web.json_response(await _xcall(
            "xpack", "enrich_get_policy", request.match_info.get("name")))

    @handler
    async def enrich_delete(request):
        return web.json_response(await _xcall(
            "xpack", "enrich_delete_policy", request.match_info["name"]))

    # ---- inference -------------------------------------------------------

    @handler
    async def inference_put(request):
        body = await body_json(request, {}) or {}
        task_type = request.match_info.get("task_type", "text_embedding")
        return web.json_response(await call(
            engine.inference.put, request.match_info["id"], task_type, body
        ))

    @handler
    async def inference_get(request):
        return web.json_response(await call(
            engine.inference.get, request.match_info.get("id")
        ))

    @handler
    async def inference_delete(request):
        return web.json_response(await call(
            engine.inference.delete, request.match_info["id"]
        ))

    @handler
    async def inference_infer(request):
        body = await body_json(request, {}) or {}
        if "input" not in body:
            raise IllegalArgumentError("[input] is required")
        return web.json_response(await call(
            engine.inference.infer,
            request.match_info["id"],
            body["input"],
            request.match_info.get("task_type"),
            body.get("query"),
        ))

    @handler
    async def health_report_api(request):
        return web.json_response(await _xcall("xpack", "health_report"))

    # ---- machine learning (_ml) ------------------------------------------
    # reference behavior: x-pack/plugin/ml rest/job/RestPutJobAction etc. —
    # jobs + datafeeds + results + model snapshots under /_ml

    @handler
    async def ml_put_job(request):
        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            engine.ml.put_job, request.match_info["job_id"], body))

    @handler
    async def ml_get_jobs(request):
        return web.json_response(await call(
            engine.ml.get_jobs, request.match_info.get("job_id")))

    @handler
    async def ml_delete_job(request):
        return web.json_response(await call(
            engine.ml.delete_job, request.match_info["job_id"],
            _bool_param(request.query, "force")))

    @handler
    async def ml_open_job(request):
        return web.json_response(await call(
            engine.ml.open_job, request.match_info["job_id"]))

    @handler
    async def ml_close_job(request):
        return web.json_response(await call(
            engine.ml.close_job, request.match_info["job_id"],
            _bool_param(request.query, "force")))

    @handler
    async def ml_flush_job(request):
        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            engine.ml.flush_job, request.match_info["job_id"], body))

    @handler
    async def ml_job_stats(request):
        return web.json_response(await call(
            engine.ml.job_stats, request.match_info.get("job_id")))

    @handler
    async def ml_get_records(request):
        from ..ml import results as ml_results

        body = await body_json(request, {}) or {}
        for p in ("start", "end", "record_score", "sort", "desc"):
            if p in request.query and p not in body:
                body[p] = request.query[p]
        return web.json_response(await call(
            ml_results.get_records, engine, request.match_info["job_id"], body))

    @handler
    async def ml_get_buckets(request):
        from ..ml import results as ml_results

        body = await body_json(request, {}) or {}
        for p in ("start", "end", "anomaly_score", "sort", "desc"):
            if p in request.query and p not in body:
                body[p] = request.query[p]
        return web.json_response(await call(
            ml_results.get_buckets, engine, request.match_info["job_id"],
            body, request.match_info.get("timestamp")))

    @handler
    async def ml_get_overall_buckets(request):
        from ..ml import results as ml_results

        body = await body_json(request, {}) or {}
        for p in ("start", "end", "overall_score"):
            if p in request.query and p not in body:
                body[p] = request.query[p]
        expr = request.match_info["job_id"]
        if expr in ("_all", "*"):
            job_ids = sorted(engine.ml._jobs())
        else:
            job_ids = [j for j in expr.split(",")]
        return web.json_response(await call(
            ml_results.get_overall_buckets, engine, job_ids, body))

    @handler
    async def ml_get_model_snapshots(request):
        return web.json_response(await call(
            engine.ml.get_model_snapshots, request.match_info["job_id"]))

    @handler
    async def ml_revert_model_snapshot(request):
        return web.json_response(await call(
            engine.ml.revert_model_snapshot, request.match_info["job_id"],
            request.match_info["snapshot_id"]))

    @handler
    async def ml_put_datafeed(request):
        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            engine.ml.put_datafeed, request.match_info["datafeed_id"], body))

    @handler
    async def ml_get_datafeeds(request):
        return web.json_response(await call(
            engine.ml.get_datafeeds, request.match_info.get("datafeed_id")))

    @handler
    async def ml_delete_datafeed(request):
        return web.json_response(await call(
            engine.ml.delete_datafeed, request.match_info["datafeed_id"]))

    @handler
    async def ml_start_datafeed(request):
        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            engine.ml.start_datafeed, request.match_info["datafeed_id"],
            request.query.get("start", body.get("start")),
            request.query.get("end", body.get("end"))))

    @handler
    async def ml_stop_datafeed(request):
        return web.json_response(await call(
            engine.ml.stop_datafeed, request.match_info["datafeed_id"]))

    @handler
    async def ml_datafeed_stats(request):
        return web.json_response(await call(
            engine.ml.datafeed_stats, request.match_info.get("datafeed_id")))

    @handler
    async def ml_preview_datafeed(request):
        return web.json_response(await call(
            engine.ml.preview_datafeed, request.match_info["datafeed_id"]))

    @handler
    async def ml_info(request):
        return web.json_response(await call(engine.ml.info))

    # ---- transform / downsample / CCS ------------------------------------

    @handler
    async def transform_put(request):
        from .. import transform as tf

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            tf.put_transform, engine, request.match_info["id"], body))

    @handler
    async def transform_get(request):
        from .. import transform as tf

        return web.json_response(await call(
            tf.get_transform, engine, request.match_info.get("id")))

    @handler
    async def transform_stats(request):
        from .. import transform as tf

        return web.json_response(await call(
            tf.get_transform_stats, engine, request.match_info["id"]))

    @handler
    async def transform_delete(request):
        from .. import transform as tf

        return web.json_response(await call(
            tf.delete_transform, engine, request.match_info["id"]))

    @handler
    async def transform_start(request):
        from .. import transform as tf

        return web.json_response(await call(
            tf.start_transform, engine, request.match_info["id"]))

    @handler
    async def transform_stop(request):
        from .. import transform as tf

        return web.json_response(await call(
            tf.stop_transform, engine, request.match_info["id"]))

    @handler
    async def transform_preview(request):
        from .. import transform as tf

        body = await body_json(request, {}) or {}
        return web.json_response(await call(tf.preview_transform, engine, body))

    @handler
    async def downsample_api(request):
        from ..transform import downsample

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            downsample, engine, request.match_info["index"],
            request.match_info["target"], body))

    @handler
    async def remote_info(request):
        remotes = engine.remote_clusters()
        return web.json_response({
            alias: {
                "connected": True, "mode": "proxy", "proxy_address": url,
                "num_proxy_sockets_connected": 1, "skip_unavailable": False,
            }
            for alias, url in remotes.items()
        })

    # ---- security --------------------------------------------------------

    @handler
    async def security_authenticate(request):
        principal = request.get("principal")
        if principal is None:
            # security disabled: anonymous superuser view
            principal = {"username": "_anonymous", "roles": ["superuser"],
                         "authentication_type": "anonymous"}
        u = engine.security.store["users"].get(principal["username"], {})
        return web.json_response({
            "username": principal["username"],
            "roles": principal["roles"],
            "full_name": u.get("full_name"),
            "email": u.get("email"),
            "metadata": u.get("metadata", {}),
            "enabled": True,
            "authentication_realm": {"name": "native", "type": "native"},
            "authentication_type": principal.get("authentication_type", "realm"),
        })

    @handler
    async def security_put_user(request):
        body = await body_json(request, {}) or {}
        return web.json_response(
            engine.security.put_user(request.match_info["name"], body))

    @handler
    async def security_get_user(request):
        return web.json_response(
            engine.security.get_user(request.match_info.get("name")))

    @handler
    async def security_delete_user(request):
        return web.json_response(
            engine.security.delete_user(request.match_info["name"]))

    @handler
    async def security_change_password(request):
        body = await body_json(request, {}) or {}
        name = request.match_info.get("name") or request.get(
            "principal", {}).get("username")
        if not body.get("password"):
            raise IllegalArgumentError("password is required")
        engine.security.change_password(name, body["password"])
        return web.json_response({})

    @handler
    async def security_put_role(request):
        body = await body_json(request, {}) or {}
        return web.json_response(
            engine.security.put_role(request.match_info["name"], body))

    @handler
    async def security_get_role(request):
        return web.json_response(
            engine.security.get_role(request.match_info.get("name")))

    @handler
    async def security_delete_role(request):
        return web.json_response(
            engine.security.delete_role(request.match_info["name"]))

    @handler
    async def security_create_api_key(request):
        body = await body_json(request, {}) or {}
        principal = request.get("principal") or {}
        username = principal.get("username", "_anonymous")
        return web.json_response(
            engine.security.create_api_key(username, body,
                                           principal=principal or None))

    def _is_key_manager(request):
        """manage_security holders see/invalidate all keys; everyone else
        only their own (reference behavior: own-API-key privileges)."""
        principal = request.get("principal")
        if principal is None:
            return True, None  # security disabled
        from ..security import AuthorizationError

        try:
            engine.security.authorize(principal, "cluster:manage_security", [])
            return True, principal["username"]
        except AuthorizationError:
            return False, principal["username"]

    @handler
    async def security_get_api_keys(request):
        manager, username = _is_key_manager(request)
        out = engine.security.get_api_keys()
        if not manager:
            out["api_keys"] = [k for k in out["api_keys"]
                               if k["username"] == username]
        return web.json_response(out)

    @handler
    async def security_invalidate_api_key(request):
        body = await body_json(request, {}) or {}
        manager, username = _is_key_manager(request)
        return web.json_response(engine.security.invalidate_api_key(
            key_id=body.get("id") or (body.get("ids") or [None])[0],
            name=body.get("name"),
            owner=None if manager else username,
        ))

    # ---- ESQL / SQL / EQL ------------------------------------------------

    @handler
    async def esql_api(request):
        # PR 20: every ESQL query is a registered cancellable task —
        # cancellation is checked between pipe operators, so POST
        # /_tasks/{id}/_cancel stops a running pipeline at the next
        # stage boundary and the 400 carries `cancelled: true`
        from ..esql import esql_query

        body = await body_json(request, {}) or {}
        task = engine.tasks.register(
            "indices:data/read/esql",
            f"esql[{str(body.get('query') or '')[:120]}]",
            cancellable=True)
        try:
            return web.json_response(
                await call(esql_query, engine, body, task=task))
        finally:
            engine.tasks.unregister(task)

    @handler
    async def sql_api(request):
        from ..esql.sql import sql_query

        body = await body_json(request, {}) or {}
        return web.json_response(await call(sql_query, engine, body))

    @handler
    async def eql_api(request):
        from ..esql.eql import eql_search

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            eql_search, engine, request.match_info["index"], body))

    # ---- async search ----------------------------------------------------
    # reference behavior: x-pack/plugin/async-search
    # TransportSubmitAsyncSearchAction.java:41 — submit returns within
    # wait_for_completion_timeout or hands back an id; results are kept
    # keep_alive long (here: in-memory store with expiry)

    app["async_searches"] = {}

    def _async_gc():
        import time as _t

        now = _t.time()
        store = app["async_searches"]
        for k in [k for k, v in store.items() if v.get("expires", 1e18) < now]:
            store.pop(k, None)

    def _async_envelope(sid, entry):
        out = {
            "id": sid,
            "is_partial": entry.get("response") is None,
            "is_running": entry["is_running"],
            "start_time_in_millis": entry["start_ms"],
            "expiration_time_in_millis": int(entry["expires"] * 1000),
        }
        if entry.get("response") is not None:
            out["response"] = entry["response"]
            out["is_partial"] = False
        if entry.get("error") is not None:
            out["error"] = entry["error"]
        return out

    @handler
    async def submit_async_search(request):
        import secrets
        import time as _t

        from ..utils.durations import parse_duration_seconds

        _async_gc()
        body = await body_json(request, {}) or {}
        wait_s = parse_duration_seconds(
            request.query.get("wait_for_completion_timeout"), 1.0)
        keep_s = parse_duration_seconds(request.query.get("keep_alive"), 300.0)
        sid = secrets.token_urlsafe(16)
        entry = {
            "is_running": True, "start_ms": int(_t.time() * 1000),
            "expires": _t.time() + (keep_s or 300.0),
            "response": None, "error": None,
        }
        app["async_searches"][sid] = entry

        async def run():
            try:
                entry["response"] = await _run_search(
                    request.match_info.get("index"), body, request.query)
            except ElasticsearchTpuError as ex:
                entry["error"] = ex.to_dict()["error"]
            except Exception as ex:  # noqa: BLE001
                entry["error"] = {"type": "exception", "reason": str(ex)}
            finally:
                entry["is_running"] = False

        task = asyncio.create_task(run())
        wait_timeout = 1.0 if wait_s is None else wait_s
        if wait_timeout > 0:
            try:
                await asyncio.wait_for(asyncio.shield(task), timeout=wait_timeout)
            except asyncio.TimeoutError:
                pass
        else:
            await asyncio.sleep(0)  # give the task a chance to start
        return web.json_response(_async_envelope(sid, entry))

    @handler
    async def get_async_search(request):
        _async_gc()
        sid = request.match_info["id"]
        entry = app["async_searches"].get(sid)
        if entry is None:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"async search [{sid}] not found")
        if request.query.get("keep_alive"):
            import time as _t

            from ..utils.durations import parse_duration_seconds

            entry["expires"] = _t.time() + (
                parse_duration_seconds(request.query["keep_alive"], 300.0) or 300.0)
        return web.json_response(_async_envelope(sid, entry))

    @handler
    async def get_async_search_status(request):
        sid = request.match_info["id"]
        entry = app["async_searches"].get(sid)
        if entry is None:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"async search [{sid}] not found")
        env = _async_envelope(sid, entry)
        env.pop("response", None)
        if not entry["is_running"] and entry.get("error") is None:
            env["completion_status"] = 200
        return web.json_response(env)

    @handler
    async def delete_async_search(request):
        sid = request.match_info["id"]
        if app["async_searches"].pop(sid, None) is None:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"async search [{sid}] not found")
        return web.json_response({"acknowledged": True})

    # ---- data streams / rollover / ILM -----------------------------------

    @handler
    async def put_data_stream(request):
        from ..engine import lifecycle

        return web.json_response(await call(
            lifecycle.create_data_stream, engine, request.match_info["name"]))

    @handler
    async def get_data_stream(request):
        from ..engine import lifecycle

        return web.json_response(await call(
            lifecycle.get_data_streams, engine, request.match_info.get("name")))

    @handler
    async def delete_data_stream(request):
        from ..engine import lifecycle

        return web.json_response(await call(
            lifecycle.delete_data_stream, engine, request.match_info["name"]))

    @handler
    async def rollover_api(request):
        from ..engine import lifecycle

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            lifecycle.rollover, engine, request.match_info["target"], body,
            _bool_param(request.query, "dry_run"),
        ))

    @handler
    async def ilm_put_policy(request):
        from ..engine import lifecycle

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            lifecycle.put_policy, engine, request.match_info["name"], body))

    @handler
    async def ilm_get_policy(request):
        from ..engine import lifecycle

        return web.json_response(await call(
            lifecycle.get_policy, engine, request.match_info.get("name")))

    @handler
    async def ilm_delete_policy(request):
        from ..engine import lifecycle

        return web.json_response(await call(
            lifecycle.delete_policy, engine, request.match_info["name"]))

    @handler
    async def ilm_explain(request):
        from ..engine import lifecycle

        return web.json_response(await call(
            lifecycle.explain, engine, request.match_info["index"]))

    @handler
    async def rank_eval_api(request):
        from ..search.rankeval import rank_eval

        body = await body_json(request, {}) or {}
        return web.json_response(await call(rank_eval, engine, body))

    @handler
    async def analyze_api(request):
        from ..engine import admin

        body = await body_json(request, {}) or {}
        # GET variant allows text/analyzer as query params
        for p in ("text", "analyzer", "field"):
            if p in request.query and p not in body:
                body[p] = request.query[p]
        return web.json_response(
            await call(admin.analyze, engine, request.match_info.get("index"), body)
        )

    @handler
    async def validate_query_api(request):
        from ..engine import admin

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            admin.validate_query, engine, request.match_info.get("index"),
            body, _bool_param(request.query, "explain"),
        ))

    @handler
    async def termvectors_api(request):
        from ..engine import admin

        body = await body_json(request, None)
        return web.json_response(await call(
            admin.termvectors, engine, request.match_info["index"],
            request.match_info["id"], body, request.query.get("fields"),
        ))

    @handler
    async def index_stats_api(request):
        from ..engine import admin

        return web.json_response(
            await call(admin.index_stats, engine, request.match_info.get("index"))
        )

    @handler
    async def index_segments_api(request):
        from ..engine import admin

        return web.json_response(
            await call(admin.index_segments, engine, request.match_info.get("index"))
        )

    @handler
    async def cluster_state_api(request):
        from ..engine import admin

        return web.json_response(await call(
            admin.cluster_state, engine, request.match_info.get("metrics")
        ))

    @handler
    async def cluster_stats_api(request):
        from ..engine import admin

        return web.json_response(await call(admin.cluster_stats, engine))

    @handler
    async def nodes_info_api(request):
        from ..engine import admin

        return web.json_response(await call(admin.nodes_info, engine))

    @handler
    async def resolve_index_api(request):
        from ..engine import admin

        return web.json_response(await call(
            admin.resolve_index, engine, request.match_info["name"]
        ))

    def _cat_endpoint(rows_fn):
        @handler
        async def cat(request):
            from ..engine import admin

            rows = await call(rows_fn, request)
            text, ctype = admin.cat_render(rows, request.query)
            return web.Response(text=text, content_type=ctype)

        return cat

    from ..engine import admin as _admin

    cat_health_api = _cat_endpoint(lambda req: _admin.cat_health(engine))
    cat_nodes_api = _cat_endpoint(lambda req: _admin.cat_nodes(engine))
    cat_count_api = _cat_endpoint(
        lambda req: _admin.cat_count(engine, req.match_info.get("index"))
    )
    cat_shards_api = _cat_endpoint(
        lambda req: _admin.cat_shards(engine, req.match_info.get("index"))
    )
    cat_aliases_api = _cat_endpoint(lambda req: _admin.cat_aliases(engine))
    cat_templates_api = _cat_endpoint(lambda req: _admin.cat_templates(engine))
    cat_allocation_api = _cat_endpoint(lambda req: _admin.cat_allocation(engine))
    cat_master_api = _cat_endpoint(lambda req: _admin.cat_master(engine))
    cat_recovery_api = _cat_endpoint(lambda req: _admin.cat_recovery(engine))
    cat_plugins_api = _cat_endpoint(lambda req: _admin.cat_plugins(engine))
    cat_tasks_api = _cat_endpoint(lambda req: _admin.cat_tasks(engine))
    cat_tenants_api = _cat_endpoint(lambda req: _admin.cat_tenants(engine))

    # ---- task management -------------------------------------------------

    def _tasks_by_node(tasks, detailed: bool = True):
        return {
            "nodes": {
                engine.tasks.node: {
                    "name": engine.tasks.node,
                    "transport_address": "127.0.0.1:9300",
                    "tasks": {t.task_id: t.to_dict(detailed=detailed)
                              for t in tasks},
                }
            }
        } if tasks else {"nodes": {}}

    @handler
    async def tasks_list(request):
        tasks = engine.tasks.list(
            actions=request.query.get("actions"),
            parent_task_id=request.query.get("parent_task_id"),
        )
        # ?detailed=true adds description + human running_time (reference
        # behavior: TransportListTasksAction detailed flag)
        detailed = request.query.get("detailed") in ("", "true", "1")
        return web.json_response(_tasks_by_node(tasks, detailed=detailed))

    @handler
    async def tasks_get(request):
        task_id = request.match_info["task_id"]
        stored = engine.tasks.get_result(task_id)
        if stored is not None:
            return web.json_response(stored)
        t = engine.tasks.get(task_id)
        return web.json_response({"completed": False, "task": t.to_dict()})

    @handler
    async def tasks_cancel(request):
        task_id = request.match_info.get("task_id")
        if task_id:
            cancelled = engine.tasks.cancel(task_id)
        else:
            cancelled = engine.tasks.cancel_matching(request.query.get("actions"))
        return web.json_response(_tasks_by_node(cancelled))

    # ---- bulk ------------------------------------------------------------

    @handler
    async def bulk(request):
        default_index = request.match_info.get("index")
        body = await request.read()
        ops = []
        lines = body.decode("utf-8").split("\n")
        i = 0
        while i < len(lines):
            line = lines[i].strip()
            i += 1
            if not line:
                continue
            action_line = json.loads(line)
            (action, meta), = action_line.items()
            if action not in ("index", "create", "delete", "update"):
                raise IllegalArgumentError(f"Malformed action/metadata line: unknown action [{action}]")
            index_name = meta.get("_index", default_index)
            if not index_name:
                raise IllegalArgumentError("bulk item missing _index")
            doc_id = meta.get("_id")
            if doc_id is not None:
                doc_id = str(doc_id)
            source = None
            if action != "delete":
                while i < len(lines) and not lines[i].strip():
                    i += 1
                if i >= len(lines):
                    raise IllegalArgumentError("bulk action missing source line")
                source = json.loads(lines[i])
                i += 1
            ops.append((action, index_name, doc_id, source,
                        meta.get("routing", meta.get("_routing"))))
        import time

        t0 = time.monotonic()
        res = await call(engine.bulk, ops, request.query.get("pipeline"))
        try:
            # per-tenant ingest metering (PR 19): the raw NDJSON byte
            # count is free here (already read) and engine.bulk never
            # sees the wire form — the ONE place ingest bytes are exact
            from ..telemetry import current_trace
            from ..tenancy.metering import normalize_tenant

            engine.metering.note_ingest(
                normalize_tenant(
                    getattr(current_trace(), "task_id", None)),
                len(body), docs=len(ops))
        except Exception:  # noqa: BLE001 - metering must not fail a bulk
            pass
        if request.query.get("refresh") in ("", "true", "wait_for"):
            for touched in {op[1] for op in ops}:
                try:
                    await call(_concrete(touched).refresh)
                except ElasticsearchTpuError:
                    pass  # e.g. every item for this index failed to index
        res["took"] = int((time.monotonic() - t0) * 1000)
        return web.json_response(res)

    # ---- ingest pipelines ------------------------------------------------

    @handler
    async def put_pipeline(request):
        body = await body_json(request, {})
        return web.json_response(
            await call(engine.ingest.put_pipeline, request.match_info["id"], body)
        )

    @handler
    async def get_pipeline(request):
        pid = request.match_info.get("id")
        if pid is None:
            return web.json_response(engine.ingest.pipelines)
        cfg = engine.ingest.get_pipeline_config(pid)
        if cfg is None:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"pipeline [{pid}] is missing")
        return web.json_response({pid: cfg})

    @handler
    async def delete_pipeline(request):
        found = engine.ingest.delete_pipeline(request.match_info["id"])
        if not found:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(
                f"pipeline [{request.match_info['id']}] is missing"
            )
        return web.json_response({"acknowledged": True})

    @handler
    async def simulate_pipeline(request):
        body = await body_json(request, {})
        docs = body.get("docs") or []
        pid = request.match_info.get("id")
        target = pid if pid is not None else {
            k: v for k, v in body.items() if k != "docs"
        }
        verbose = request.query.get("verbose") in ("", "true")
        return web.json_response(
            await call(engine.ingest.simulate, target, docs, verbose)
        )

    # ---- search ----------------------------------------------------------

    async def _run_search(expression, body, query_params, respond=None):
        """-> the response body; `respond` (the `_search` handler's
        `web.json_response`) turns it into the response inside the
        `rest.respond` span, so that span holds the JSON encoding too."""
        def answer(out):
            return out if respond is None else respond(out)

        body = body or {}
        if query_params.get("routing"):
            # same resolution options as the search itself, so the guard
            # cannot 404 a request ignore_unavailable would let through
            for idx, _f in engine.resolve_search(
                    expression,
                    ignore_unavailable=_bool_param(
                        query_params, "ignore_unavailable"),
                    allow_no_indices=_bool_param(
                        query_params, "allow_no_indices", True)):
                if idx.ts_mode is not None:
                    raise IllegalArgumentError(
                        f"searching with a specified routing is not "
                        f"supported because the destination index "
                        f"[{idx.name}] is in time series mode")
        if body.get("retriever") is not None:
            from ..search.rankeval import rrf_retriever_search

            import time

            t0 = time.monotonic()
            res = await call(
                rrf_retriever_search, engine, expression, body["retriever"],
                int(query_params.get("size", body.get("size", 10))),
                int(query_params.get("from", body.get("from", 0))),
            )
            return answer({
                "took": int((time.monotonic() - t0) * 1000),
                "timed_out": False,
                "_shards": {"total": 1, "successful": 1, "skipped": 0, "failed": 0},
                **res,
            })
        query = body.get("query")
        knn = body.get("knn")
        size = int(query_params.get("size", body.get("size", 10)))
        from_ = int(query_params.get("from", body.get("from", 0)))
        aggs = body.get("aggs") or body.get("aggregations")
        sort = body.get("sort")
        search_after = body.get("search_after")
        pit = body.get("pit")
        scroll = query_params.get("scroll")
        import time

        # "profile": true activates the device-cost collector around the
        # MAIN search execution (kernel call sites record tier choice,
        # Pallas wall timings, cache hits); the per-subtree profile walk
        # below runs OUTSIDE the collector so its re-executions don't
        # pollute the request's own attribution
        _prof_cm = _prof_events = None
        if body.get("profile"):
            from ..telemetry import collect_profile_events

            _prof_cm = collect_profile_events()
            _prof_events = _prof_cm.__enter__()
        t0 = time.monotonic()
        kwargs = dict(
            query=query, size=size, from_=from_, aggs=aggs, knn=knn, sort=sort,
            search_after=search_after, script_fields=body.get("script_fields"),
            collapse=body.get("collapse"), rescore=body.get("rescore"),
            runtime_mappings=body.get("runtime_mappings"),
            track_total_hits=_track_total_hits_param(body, query_params),
        )
        try:
            if pit is not None:
                if not isinstance(pit, dict) or "id" not in pit:
                    raise IllegalArgumentError("[pit] must be an object with an [id]")
                res = await call(
                    engine_search, engine.search_pit, pit["id"],
                    pit.get("keep_alive"), **kwargs
                )
            elif scroll:
                res = await call(engine_search, engine.scroll_search,
                                 expression, scroll, **kwargs)
            else:
                # continuous-batching front end: wave-eligible requests
                # ride the coalescing queue (packed device waves, tenant
                # fairness, deadlines, backpressure) instead of a solo
                # engine dispatch; everything else takes the classic path
                sv = engine.serving_if_enabled()
                entry = (sv.classify(expression, body, query_params)
                         if sv is not None and not _prof_cm else None)
                if entry is not None:
                    from ..telemetry import current_trace
                    from ..utils.durations import parse_duration_seconds

                    from ..tenancy.metering import normalize_tenant

                    tr = current_trace()
                    # X-Opaque-Id -> tenant through the ONE shared
                    # normalizer (PR 19): the queue, the meter, and the
                    # cache-accounting join all see the same key
                    tenant = normalize_tenant(getattr(tr, "task_id", None))
                    t_raw = body.get("timeout") or query_params.get("timeout")
                    if t_raw is None:
                        t_raw = engine.settings.get(
                            "search.default_search_timeout")
                    served = sv.submit(
                        entry, tenant=tenant,
                        timeout_s=parse_duration_seconds(t_raw, None))
                    res = await asyncio.wrap_future(served)
                    # the wave's stages, as this search's own
                    sv.member_spans(served)
                else:
                    res = await call(
                        engine_search, engine.search_multi, expression,
                        ignore_unavailable=_bool_param(query_params, "ignore_unavailable"),
                        allow_no_indices=_bool_param(query_params, "allow_no_indices", True),
                        **kwargs,
                    )
        finally:
            if _prof_cm is not None:
                _prof_cm.__exit__(None, None, None)
        took = int((time.monotonic() - t0) * 1000)
        with TRACER.span("rest.respond"):
            from ..telemetry import metrics as _metrics

            _metrics.counter_inc("es.search.query.total")
            _metrics.histogram_record("es.search.query.took_ms", took)
            from ..search import apply_fetch_phase

            # fetch options given as URL params (the reference accepts both)
            if "_source" in query_params and "_source" not in body:
                rs = query_params["_source"]
                body = {**body, "_source": (rs == "true") if rs in ("true", "false")
                        else rs.split(",")}
            inc = query_params.get("_source_includes")
            exc = query_params.get("_source_excludes")
            if (inc or exc) and not isinstance(body.get("_source"), dict):
                body = {**body, "_source": {
                    "includes": inc.split(",") if inc else [],
                    "excludes": exc.split(",") if exc else [],
                }}
            if "docvalue_fields" in query_params and "docvalue_fields" not in body:
                body = {**body,
                        "docvalue_fields": query_params["docvalue_fields"].split(",")}
            if "stored_fields" in query_params and "stored_fields" not in body:
                body = {**body,
                        "stored_fields": query_params["stored_fields"].split(",")}

            def _mappings_of(name):
                if ":" in name:  # remote (CCS) hit: sub-phases already applied there
                    return None
                return engine.get_index(name).mappings

            # `fields: [_tsid]` on a time-series index: computed from the full
            # source BEFORE source filtering, attached after the fetch phase
            # (never fetched by default — reference TimeSeriesIdFieldMapper)
            want_tsid = any(
                (f if isinstance(f, str) else (f or {}).get("field")) == "_tsid"
                for f in (body.get("fields") or []))
            tsids = {}
            if want_tsid:
                for pos, hit in enumerate(res["hits"]["hits"]):
                    tsm = getattr(engine.indices.get(hit.get("_index")),
                                  "ts_mode", None)
                    if tsm is not None and hit.get("_source"):
                        tsids[pos] = tsm.tsid_of(hit["_source"])
            _t_fetch = time.monotonic()
            apply_fetch_phase(res["hits"]["hits"], body, _mappings_of)
            _fetch_ms = (time.monotonic() - _t_fetch) * 1000
            for pos, tsid in tsids.items():
                res["hits"]["hits"][pos].setdefault("fields", {})["_tsid"] = [
                    tsid]
            if body.get("suggest"):
                res["suggest"] = await call(
                    engine.suggest_multi, expression, body["suggest"]
                )
            if body.get("profile"):
                # per-query profile TREE with measured per-subtree timings
                # (reference behavior: search/profile/query/QueryProfiler —
                # every node reports type/description/breakdown/children).
                # Each subtree times as its own device program: create_weight
                # carries the trace+compile cost, score the fused execution.
                def _profile():
                    from ..query.dsl import parse_query
                    from ..search.profile import empty_shard, profile_shards

                    shards = []
                    took_ns = int((time.monotonic() - t0) * 1e9)
                    phases = {"query_ms": took, "fetch_ms": round(_fetch_ms, 3)}
                    for idx, alias_filter in engine.resolve_search(
                        expression or "_all", True, True
                    ):
                        if idx.searcher is None:
                            # never-refreshed index: the shard entry must still
                            # exist (clients index into profile.shards)
                            shards.append(empty_shard(idx, engine.tasks.node))
                            continue
                        q = body.get("query") or {"match_all": {}}
                        if alias_filter:
                            # profile the query that actually executed: a
                            # filtered alias ANDs its filter in
                            q = {"bool": {"must": [q],
                                          "filter": [alias_filter]}}
                        node = parse_query(q, idx.mappings)
                        shards.extend(
                            profile_shards(idx, node, took_ns, engine.tasks.node,
                                           device_events=_prof_events,
                                           phases=phases)
                        )
                    return {"shards": shards}

                res["profile"] = await call(_profile)
            try:
                n_shards = sum(
                    i.num_shards for i, _ in engine.resolve_search(
                        expression, _bool_param(query_params, "ignore_unavailable"), True
                    )
                )
            except ElasticsearchTpuError:
                n_shards = 1  # e.g. remote-cluster expressions resolve elsewhere
            if _bool_param(query_params, "rest_total_hits_as_int"):
                tot = res.get("hits", {}).get("total")
                if isinstance(tot, dict):
                    res["hits"]["total"] = tot["value"]
            skipped = res.pop("skipped_shards", 0)
            # honest `_shards` (PR 14): the fan-out reports its real outcome —
            # failed shards + attributed failures ride the engine result, and
            # allow_partial_search_results (body > query param > dynamic
            # cluster default, ES semantics: default true) decides whether a
            # partial response is served or the request fails with 503
            failed = res.pop("failed_shards", 0)
            failures = res.pop("shard_failures", None)
            if failed:
                allow = body.get("allow_partial_search_results")
                if allow is None:
                    raw = query_params.get("allow_partial_search_results")
                    if raw is not None:
                        allow = raw in ("", "true", "1")
                if allow is None:
                    allow = bool(engine.settings.get(
                        "search.default_allow_partial_results"))
                if not allow:
                    from ..utils.errors import SearchPhaseExecutionError

                    raise SearchPhaseExecutionError(
                        f"{failed} shard failure(s) and "
                        "allow_partial_search_results is false",
                        failures=failures)
            shards = {
                "total": n_shards,
                # the reference counts skipped shards as successful too
                "successful": max(n_shards - failed, 0),
                "skipped": skipped,
                "failed": failed,
            }
            if failures:
                shards["failures"] = failures
            return answer({
                "took": took,
                "timed_out": False,
                "_shards": shards,
                **res,
            })

    @handler
    async def search(request):
        with TRACER.span("rest.search"):
            body = await body_json(request, {})
            return await _run_search(request.match_info.get("index"), body,
                                     request.query, web.json_response)

    @handler
    async def msearch(request):
        raw = (await request.read()).decode("utf-8")
        lines = [ln for ln in raw.split("\n") if ln.strip()]
        if len(lines) % 2 != 0:
            raise IllegalArgumentError("msearch body must be header/body line pairs")

        async def one(name, body, shared):
            try:
                return {**(await _run_search(name, body, shared)),
                        "status": 200}
            except ElasticsearchTpuError as ex:
                return {**ex.to_dict(), "status": ex.status}

        subs = []
        for i in range(0, len(lines), 2):
            header = json.loads(lines[i])
            body = json.loads(lines[i + 1])
            name = header.get("index", request.match_info.get("index"))
            # only the reference's msearch-level params apply to every
            # sub-search; size/from/scroll etc. stay per-body
            shared = {k: request.query[k]
                      for k in ("rest_total_hits_as_int", "typed_keys")
                      if k in request.query}
            subs.append((name, body, shared))
        if engine.serving_if_enabled() is not None and len(subs) > 1:
            # concurrent submission: the serving queue coalesces the
            # sub-searches into one device wave instead of N dispatches
            responses = list(await asyncio.gather(
                *(one(*s) for s in subs)))
        else:
            responses = [await one(*s) for s in subs]
        return web.json_response({"took": 0, "responses": responses})

    @handler
    async def count(request):
        body = await body_json(request, {}) or {}
        expression = request.match_info.get("index")
        failures: list = []
        n = await call(engine.count_multi, expression, body.get("query"),
                       failures)
        n_shards = sum(i.num_shards for i, _ in engine.resolve_search(expression))
        failed = sum(
            engine.indices[f["index"]].num_shards
            if f["index"] in engine.indices else 1 for f in failures)
        shards = {"total": n_shards,
                  "successful": max(n_shards - failed, 0),
                  "skipped": 0, "failed": failed}
        if failures:
            shards["failures"] = failures
        return web.json_response({"count": n, "_shards": shards})

    @handler
    async def scroll_continue(request):
        body = await body_json(request, {}) or {}
        sid = body.get("scroll_id") or request.query.get("scroll_id") \
            or request.match_info.get("scroll_id")
        if not sid:
            raise IllegalArgumentError("scroll_id is required")
        scroll = body.get("scroll") or request.query.get("scroll")
        res = await call(engine.continue_scroll, sid, scroll)
        res.pop("skipped_shards", None)  # internal coordinator detail
        return web.json_response({"took": 0, "timed_out": False, **res})

    @handler
    async def scroll_clear(request):
        sid = request.match_info.get("scroll_id")
        if sid is None:
            body = await body_json(request, {}) or {}
            sid = body.get("scroll_id", "_all")
        n = await call(engine.clear_scroll, sid)
        return web.json_response({"succeeded": True, "num_freed": n})

    @handler
    async def open_pit(request):
        keep_alive = request.query.get("keep_alive")
        if not keep_alive:
            raise IllegalArgumentError("[keep_alive] is required")
        pit_id = await call(engine.open_pit, request.match_info["index"], keep_alive)
        return web.json_response({"id": pit_id})

    @handler
    async def close_pit(request):
        body = await body_json(request, {}) or {}
        pit_id = body.get("id")
        if not pit_id:
            raise IllegalArgumentError("[id] is required")
        found = await call(engine.close_pit, pit_id)
        return web.json_response(
            {"succeeded": found, "num_freed": 1 if found else 0},
            status=200 if found else 404,
        )

    @handler
    async def mget(request):
        body = await body_json(request, {}) or {}
        default_index = request.match_info.get("index")
        from ..utils.errors import ActionRequestValidationError

        items = []
        specs = []
        if "docs" in body:
            for d in body["docs"]:
                name = d.get("_index", default_index)
                if not name:
                    raise ActionRequestValidationError("index is missing")
                if "_id" not in d:
                    raise ActionRequestValidationError("id is missing")
                items.append((name, str(d["_id"])))
                specs.append(d.get("_source"))
        elif "ids" in body:
            if not default_index:
                raise IllegalArgumentError("ids form requires an index in the path")
            items = [(default_index, str(i)) for i in body["ids"]]
            specs = [None] * len(items)
        else:
            raise IllegalArgumentError("unexpected content, expected [docs] or [ids]")
        # request-level _source controls (per-doc specs win)
        req_spec = None
        if request.query.get("_source") is not None:
            rs = request.query["_source"]
            req_spec = (rs == "true") if rs in ("true", "false") else rs.split(",")
        inc = request.query.get("_source_includes")
        exc = request.query.get("_source_excludes")
        if inc or exc:
            req_spec = {"includes": inc.split(",") if inc else [],
                        "excludes": exc.split(",") if exc else []}
        docs = await call(engine.mget, items)
        if req_spec is not None or any(s is not None for s in specs):
            from ..search.fetch import filter_source

            for doc, spec in zip(docs, specs):
                spec = spec if spec is not None else req_spec
                if spec is None or "_source" not in doc:
                    continue
                filtered = filter_source(doc["_source"], spec)
                if filtered is None:
                    doc.pop("_source", None)
                else:
                    doc["_source"] = filtered
        return web.json_response({"docs": docs})

    @handler
    async def explain_doc(request):
        body = await body_json(request, {}) or {}
        q = body.get("query")
        if q is None and request.query.get("q") is None:
            raise IllegalArgumentError("query is missing")
        idx = _concrete(request.match_info["index"])
        res = await call(idx.explain, request.match_info["id"], q)
        return web.json_response({"_index": idx.name, **res})

    @handler
    async def field_caps(request):
        body = await body_json(request, {}) or {}
        fields = request.query.get("fields") or body.get("fields") or "*"
        res = await call(
            engine.field_caps, request.match_info.get("index"), fields
        )
        return web.json_response(res)

    # ---- aliases ---------------------------------------------------------

    @handler
    async def post_aliases(request):
        body = await body_json(request, {}) or {}
        actions = body.get("actions")
        if not isinstance(actions, list):
            raise IllegalArgumentError("No action specified")
        return web.json_response(await call(engine.update_aliases, actions))

    @handler
    async def put_alias(request):
        name = request.match_info["index"]
        alias = request.match_info["alias"]
        body = await body_json(request, {}) or {}
        action = {"add": {"index": name, "alias": alias, **body}}
        return web.json_response(await call(engine.update_aliases, [action]))

    @handler
    async def delete_alias(request):
        action = {"remove": {
            "index": request.match_info["index"],
            "alias": request.match_info["alias"],
        }}
        return web.json_response(await call(engine.update_aliases, [action]))

    def _alias_table(index_pattern=None, alias_pattern=None):
        import fnmatch

        out = {}
        for name, idx in engine.indices.items():
            if index_pattern and not any(
                fnmatch.fnmatchcase(name, p) for p in index_pattern.split(",")
            ):
                continue
            aliases = engine.meta.aliases_of(name)
            if alias_pattern is not None:
                aliases = {
                    a: p for a, p in aliases.items()
                    if any(fnmatch.fnmatchcase(a, ap) for ap in alias_pattern.split(","))
                }
                if not aliases:
                    continue
            out[name] = {"aliases": {
                a: {k: v for k, v in p.items() if v is not None}
                for a, p in aliases.items()
            }}
        return out

    @handler
    async def get_alias(request):
        index_pattern = request.match_info.get("index")
        alias_pattern = request.match_info.get("alias")
        table = _alias_table(index_pattern, alias_pattern)
        if alias_pattern is not None and not table:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"alias [{alias_pattern}] missing")
        return web.json_response(table)

    @handler
    async def head_alias(request):
        table = _alias_table(request.match_info.get("index"), request.match_info["alias"])
        return web.Response(status=200 if table else 404)

    # ---- templates -------------------------------------------------------

    @handler
    async def put_index_template(request):
        body = await body_json(request, {}) or {}
        await call(engine.meta.put_index_template, request.match_info["name"], body)
        return web.json_response({"acknowledged": True})

    @handler
    async def get_index_template(request):
        import fnmatch

        pattern = request.match_info.get("name", "*")
        matched = [
            {"name": n, "index_template": b}
            for n, b in sorted(engine.meta.index_templates.items())
            if fnmatch.fnmatchcase(n, pattern)
        ]
        if not matched and "*" not in pattern:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"index template matching [{pattern}] not found")
        return web.json_response({"index_templates": matched})

    @handler
    async def head_index_template(request):
        import fnmatch

        pattern = request.match_info["name"]
        ok = any(fnmatch.fnmatchcase(n, pattern) for n in engine.meta.index_templates)
        return web.Response(status=200 if ok else 404)

    @handler
    async def delete_index_template(request):
        await call(engine.meta.delete_index_template, request.match_info["name"])
        return web.json_response({"acknowledged": True})

    @handler
    async def put_component_template(request):
        body = await body_json(request, {}) or {}
        await call(engine.meta.put_component_template, request.match_info["name"], body)
        return web.json_response({"acknowledged": True})

    @handler
    async def get_component_template(request):
        import fnmatch

        pattern = request.match_info.get("name", "*")
        matched = [
            {"name": n, "component_template": b}
            for n, b in sorted(engine.meta.component_templates.items())
            if fnmatch.fnmatchcase(n, pattern)
        ]
        if not matched and "*" not in pattern:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"component template matching [{pattern}] not found")
        return web.json_response({"component_templates": matched})

    @handler
    async def delete_component_template(request):
        await call(engine.meta.delete_component_template, request.match_info["name"])
        return web.json_response({"acknowledged": True})

    @handler
    async def simulate_index_template(request):
        name = request.match_info["name"]
        composed = engine.meta.compose_for_index(name)
        return web.json_response({"template": {
            "settings": composed.get("settings", {}),
            "mappings": composed.get("mappings", {}),
            "aliases": composed.get("aliases", {}),
        }, "overlapping": []})

    # ---- settings --------------------------------------------------------

    @handler
    async def get_cluster_settings(request):
        body = {
            "persistent": dict(engine.settings.persistent),
            "transient": dict(engine.settings.transient),
        }
        if _bool_param(request.query, "include_defaults"):
            body["defaults"] = {
                k: s.default for k, s in engine.settings.registry.items()
                if k not in engine.settings.persistent
                and k not in engine.settings.transient
            }
        return web.json_response(body)

    @handler
    async def put_cluster_settings(request):
        body = await body_json(request, {}) or {}
        return web.json_response(await call(engine.settings.update, body))

    @handler
    async def get_index_settings(request):
        out = {}
        for idx, _ in engine.resolve_search(request.match_info["index"]):
            out[idx.name] = {"settings": {"index": {
                k: (str(v) if not isinstance(v, (dict, list)) else v)
                for k, v in idx.settings.items()
            }}}
        return web.json_response(out)

    @handler
    async def put_index_settings(request):
        body = await body_json(request, {}) or {}
        updates = body.get("settings", body) or {}
        if "index" in updates and isinstance(updates["index"], dict):
            updates = {**updates, **updates.pop("index")}
        res = None
        for idx, _ in engine.resolve_search(request.match_info["index"]):
            res = await call(idx.update_settings, updates)
        return web.json_response(res or {"acknowledged": True})

    # ---- snapshots -------------------------------------------------------

    @handler
    async def put_repository(request):
        body = await body_json(request, {}) or {}
        return web.json_response(
            await call(engine.snapshots.put_repository,
                       request.match_info["repo"], body)
        )

    @handler
    async def get_repository(request):
        return web.json_response(
            engine.snapshots.get_repository(request.match_info.get("repo"))
        )

    @handler
    async def delete_repository(request):
        return web.json_response(
            await call(engine.snapshots.delete_repository, request.match_info["repo"])
        )

    @handler
    async def create_snapshot(request):
        body = await body_json(request, {}) or {}
        res = await call(
            engine.snapshots.create_snapshot,
            request.match_info["repo"], request.match_info["snap"],
            body.get("indices", "*"), body.get("include_global_state", True),
        )
        return web.json_response({"snapshot": res})

    @handler
    async def get_snapshot(request):
        res = await call(
            engine.snapshots.get_snapshots,
            request.match_info["repo"], request.match_info["snap"],
        )
        return web.json_response({"snapshots": res})

    @handler
    async def delete_snapshot(request):
        return web.json_response(
            await call(engine.snapshots.delete_snapshot,
                       request.match_info["repo"], request.match_info["snap"])
        )

    @handler
    async def restore_snapshot(request):
        body = await body_json(request, {}) or {}
        return web.json_response(
            await call(engine.snapshots.restore_snapshot,
                       request.match_info["repo"], request.match_info["snap"], body)
        )

    @handler
    async def snapshot_status(request):
        return web.json_response(
            await call(engine.snapshots.status,
                       request.match_info["repo"], request.match_info["snap"])
        )

    @handler
    async def mount_snapshot(request):
        body = await body_json(request, {}) or {}
        return web.json_response(
            await call(engine.snapshots.mount_snapshot,
                       request.match_info["repo"], request.match_info["snap"],
                       body)
        )

    @handler
    async def searchable_snapshot_cache_stats(request):
        return web.json_response(engine.blob_cache.stats())

    # ---- cluster / cat ---------------------------------------------------

    @handler
    async def cluster_health(request):
        """Health derived from searcher/replica state (PR 9 — no more
        hardcoded green): red indices have no live searcher, replicas on
        a single node are unassigned (yellow). wait_for_status polls
        until the status is AT LEAST as good as requested, then 408 +
        timed_out like the reference on expiry."""
        from ..utils.durations import parse_duration_seconds

        expr = request.match_info.get("index")
        h = await call(engine.cluster_health, expr)
        want = request.query.get("wait_for_status")
        order = {"green": 0, "yellow": 1, "red": 2}
        if want in order:
            timeout_s = parse_duration_seconds(
                request.query.get("timeout", "30s"), 30.0) or 30.0
            deadline = time.monotonic() + timeout_s
            while (order[h["status"]] > order[want]
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.05)
                h = await call(engine.cluster_health, expr)
            if order[h["status"]] > order[want]:
                h["timed_out"] = True
                if request.query.get("level") != "indices":
                    h.pop("indices", None)
                return web.json_response(h, status=408)
        if request.query.get("level") != "indices":
            h.pop("indices", None)
        return web.json_response(h)

    @handler
    async def cat_indices(request):
        rows = []
        mgr = engine._superpacks  # annotate only — never build the manager
        for name, idx in sorted(engine.indices.items()):
            row = {
                "health": engine.index_health(name),
                "status": "open",
                "index": name,
                "pri": str(idx.num_shards),
                "rep": str(idx.settings.get("number_of_replicas") or 0),
                "docs.count": str(idx.live_count),
                "docs.deleted": str(sum(1 for e in idx.docs.values() if not e.alive)),
            }
            if mgr is not None:
                sp = mgr.member_stats(name)
                if sp is not None:
                    row["superpack"] = sp
            rows.append(row)
        if request.query.get("format") == "json":
            return web.json_response(rows)
        text = "\n".join(
            f"{r['health']} {r['status']} {r['index']} {r['pri']} {r['rep']} {r['docs.count']}"
            for r in rows
        )
        return web.Response(text=text + ("\n" if text else ""), content_type="text/plain")

    @handler
    async def nodes_stats(request):
        import jax

        from ..cache import request_cache
        from ..common import resilience as _resilience
        from ..monitoring import device as _mon_device
        from ..planner import execution_planner as _execution_planner
        from ..telemetry import TRACER, metrics, recent_slowlogs

        devices = [str(d) for d in jax.devices()]
        total_docs = sum(i.live_count for i in engine.indices.values())
        return web.json_response(
            {
                "_nodes": {"total": 1, "successful": 1, "failed": 0},
                "cluster_name": "elasticsearch-tpu",
                "nodes": {
                    "node-0": {
                        "name": "node-0",
                        "roles": ["master", "data", "ingest"],
                        "indices": {
                            "docs": {"count": total_docs},
                            # reference shape: indices.request_cache
                            # {memory_size_in_bytes, evictions, hit_count,
                            # miss_count} (+ framework extras)
                            "request_cache": request_cache().stats(),
                        },
                        "breakers": engine.breakers.stats(),
                        # reference shape: _nodes/stats ml section
                        # (anomaly detectors / datafeeds / model memory)
                        "ml": engine.ml.node_stats(),
                        "tpu": {"devices": devices},
                        # device-utilization accounting (monitoring/):
                        # HBM live/peak + padded waste, per-kernel
                        # cumulative MFU / bandwidth utilization, JIT
                        # compile + executable-cache counters
                        "device": _mon_device.device_stats(engine),
                        "monitoring": engine.monitoring.stats(),
                        # scheduled alerting + SLO compliance (PR 9):
                        # built lazily — a node that never used them
                        # reports the cheap placeholder, not a service
                        "watcher": (engine._watcher.stats()
                                    if engine._watcher is not None
                                    else {"watcher_state": "not_built"}),
                        "slo": (engine._slo.last_evaluation
                                if engine._slo is not None else None),
                        # continuous-batching front end: queue depth,
                        # wave occupancy, shed/expiry/cancel accounting
                        "serving": engine.serving.stats(),
                        # tenant superpacks (PR 17): members, size
                        # classes, compiled-program count, HBM bytes per
                        # tenant, padded-waste fraction — the numbers
                        # that make thousand-tenant density a reported,
                        # bounded quantity (cheap placeholder when the
                        # manager was never built)
                        "superpack": (engine._superpacks.stats()
                                      if engine._superpacks is not None
                                      else {"enabled": False,
                                            "members": 0}),
                        # data-plane resilience (PR 14): per-peer circuit
                        # breakers (state/trips), retry + failover +
                        # partial-response counters, device-degradation
                        # events and the recovery-ramp state
                        "resilience": {
                            **_resilience.resilience_stats(),
                            "device": (
                                engine._device_degradation.stats()
                                if engine._device_degradation is not None
                                else {"degraded": False}),
                        },
                        # adaptive execution planner (PR 18): per-arm
                        # decision counts and modes (model / static /
                        # repriced), per-kernel efficiency EMAs +
                        # predicted-vs-actual residuals, knob adjustment
                        # counters, currently repriced arms
                        "planner": _execution_planner().stats(),
                        # write-path ground truth (PR 13): refresh/merge
                        # counts, cumulative build-stage millis, current
                        # tail-tier fraction, refresh lag, docs/s EMA
                        "indexing": engine.indexing_stats(),
                        # per-tenant resource ledger (PR 19): exact
                        # apportioned device-ms shares, queue waits,
                        # sheds, cache + ingest traffic per tenant,
                        # bounded at metering.tenant.top_k rows + _other
                        "tenants": engine.tenant_stats(),
                        # ESQL dataflow ground truth (PR 20): cumulative
                        # per-operator walls, rows, materialization
                        # high-water marks and esql.materialization
                        # breaker trips from the per-query profiler
                        "esql": engine.esql_recorder.stats(),
                        "metrics": metrics.snapshot(),
                        # tail-latency inspection without log scraping:
                        # the most recent slowlog entries (now carrying
                        # trace_id/task_id/node) and finished root spans
                        "telemetry": {
                            "recent_slowlogs": list(recent_slowlogs)[-32:],
                            "recent_spans": TRACER.recent_spans(20),
                        },
                    }
                },
            }
        )

    @handler
    async def serving_stats(request):
        """Serving front-end introspection: queue depths per tenant,
        admission/shed/expiry/cancel counters, wave sizing + term-lane
        occupancy, backpressure configuration."""
        return web.json_response({"serving": engine.serving.stats()})

    @handler
    async def tenants_stats(request):
        """GET /_tenants/stats: the per-tenant resource ledger (PR 19)
        — exact apportioned device-ms (+ burn rate and per-kernel
        split), queue-wait p99, shed/expiry/cancel counts, request-
        cache traffic and superpack-lane bytes held, ingest volume."""
        return web.json_response({"tenants": engine.tenant_stats()})

    @handler
    async def refresh_profile(request):
        """GET /_refresh/profile: the bounded per-refresh RefreshProfile
        ring — contiguous build-stage timings summing to each refresh's
        wall time, docs/bytes processed, refresh kind, and the resulting
        tail-tier state (PR 13, the write-path twin of the serving
        flight recorder)."""
        n = request.query.get("n")
        return web.json_response(
            engine.refresh_recorder.profiles(int(n) if n else None))

    @handler
    async def esql_profile(request):
        """GET /_esql/profile: the bounded per-query OperatorProfile
        ring — contiguous per-operator timings summing exactly to each
        query's wall time, rows/pages in/out, bytes materialized per
        column, peak-live-bytes high-water and the dominant operator
        (PR 20, the ESQL twin of GET /_refresh/profile)."""
        n = request.query.get("n")
        return web.json_response(
            engine.esql_recorder.profiles(int(n) if n else None))

    @handler
    async def serving_flight_recorder(request):
        """GET /_serving/flight_recorder: the bounded per-wave ring —
        segment timings (queue/plan/device/finish summing to the wave's
        wall time), tenant/lane mix, per-kernel utilization deltas,
        cache traffic, and escalations (PR 12)."""
        n = request.query.get("n")
        return web.json_response(
            engine.serving.flight_recorder(int(n) if n else None))

    @handler
    async def serving_flight_recorder_dump(request):
        """POST /_serving/flight_recorder/_dump: persist the ring into
        the hidden daily .flight-recorder-* index (what the watcher
        `capture` action does on an SLO breach)."""
        return web.json_response(
            await call(engine.serving.dump_flight_recorder))

    @handler
    async def fault_injection_get(request):
        """GET /_fault_injection (test-only): the active schedule and its
        per-rule (checks, fired) counters — a chaos run proves its
        schedule actually fired from this body."""
        from ..common import faults

        return web.json_response(faults.stats())

    @handler
    async def fault_injection_put(request):
        """POST /_fault_injection {"spec": ..., "seed": N} (test-only):
        install a seeded fault schedule in this process. The production
        path costs one global-None check while no schedule is active."""
        from ..common import faults

        body = await body_json(request, {}) or {}
        spec = body.get("spec")
        if not spec:
            raise IllegalArgumentError("[spec] is required")
        return web.json_response(
            faults.configure(str(spec), int(body.get("seed", 0))))

    @handler
    async def fault_injection_delete(request):
        from ..common import faults

        faults.clear()
        return web.json_response({"acknowledged": True})

    @handler
    async def profiler_start(request):
        """POST /_profiler/start: begin a duration-bounded jax.profiler
        trace (body: {"duration": "2s"}); the watchdog force-stops it at
        the bound even if /stop never arrives."""
        body = await body_json(request, {}) or {}
        from ..utils.durations import parse_duration_seconds

        dur = parse_duration_seconds(body.get("duration"), None)
        out = engine.profiler.start(duration_s=dur, reason="rest")
        return web.json_response(out, status=200 if out.get("started")
                                 else 409)

    @handler
    async def profiler_stop(request):
        out = engine.profiler.stop()
        return web.json_response(out, status=200 if out.get("stopped")
                                 else 409)

    @handler
    async def profiler_status(request):
        return web.json_response(engine.profiler.status())

    @handler
    async def get_trace(request):
        """Debug endpoint: stitch every span of one trace held by this
        process into a time-ordered tree (the single-node analog of the
        cluster gateway's fan-out collection)."""
        from ..telemetry import stitch_trace

        trace_id = request.match_info["trace_id"].lower()
        spans = TRACER.spans_for_trace(trace_id)
        if not spans:
            from ..utils.errors import ResourceNotFoundError

            raise ResourceNotFoundError(f"trace [{trace_id}] not found")
        return web.json_response(stitch_trace(spans))

    @handler
    async def prometheus_metrics(request):
        """Prometheus text exposition: every registry instrument plus
        point-in-time breaker and request-cache state sampled at scrape
        time (the reference exports these through its APM metering; a
        scrape endpoint needs no agent)."""
        from ..cache import request_cache
        from ..telemetry import metrics

        extra = {}
        for name, b in engine.breakers.stats().items():
            if not isinstance(b, dict):
                continue
            extra[f"es.breaker.{name}.estimated_bytes"] = \
                b.get("estimated_size_in_bytes", 0)
            extra[f"es.breaker.{name}.limit_bytes"] = \
                b.get("limit_size_in_bytes", 0)
            extra[f"es.breaker.{name}.tripped"] = b.get("tripped", 0)
        cs = request_cache().stats()
        for key in ("memory_size_in_bytes", "evictions", "hit_count",
                    "miss_count", "entry_count"):
            if key in cs:
                extra[f"es.request_cache.{key}"] = cs[key]
        # device-utilization gauges (monitoring/): HBM residency + the
        # padded-lane waste of the fixed-shape packs; the per-kernel MFU /
        # bandwidth histograms (es.kernel.*.mfu_pct / .bw_pct) ride the
        # registry exposition above
        from ..monitoring import device as _mon_device

        mem = _mon_device.device_memory_snapshot()
        for key in ("live_bytes", "live_arrays", "bytes_in_use",
                    "peak_bytes_in_use", "bytes_limit"):
            if key in mem and mem[key] is not None:
                extra[f"es.device.hbm.{key}"] = mem[key]
        extra["es.device.pack_padded_waste_bytes"] = \
            _mon_device.padded_waste_bytes(engine)
        # write-path gauges (PR 13): tail-tier fraction + refresh lag +
        # ingest rate, scraped alongside the kernel utilization they gate
        try:
            idx_stats = engine.indexing_stats()
            extra["es.indexing.tail_fraction"] = idx_stats["tail_fraction"]
            extra["es.indexing.refresh_lag_ms"] = \
                idx_stats["refresh_lag_ms"]
            if idx_stats.get("docs_per_s_ema") is not None:
                extra["es.indexing.docs_per_s_ema"] = \
                    idx_stats["docs_per_s_ema"]
        except Exception:  # noqa: BLE001 - the scrape must not 500
            pass
        # data-plane resilience gauges (PR 14): open circuits + device
        # degradation state; the es.resilience.* counters ride the
        # registry exposition above
        try:
            from ..common.resilience import resilience_stats

            extra["es.resilience.open_circuits"] = \
                resilience_stats()["open_circuits"]
            extra["es.resilience.device_degraded"] = (
                1 if (engine._device_degradation is not None
                      and engine._device_degradation.degraded) else 0)
        except Exception:  # noqa: BLE001 - the scrape must not 500
            pass
        # closed-loop health/SLO gauges (PR 9): the scrape itself carries
        # the indicator-based health status and SLO compliance, so a
        # dashboard alert needs no extra endpoint
        try:
            from ..xpack.health import STATUS_CODES, health_report

            hr = health_report(engine)
            extra["es.health.status"] = STATUS_CODES.get(hr["status"], 1)
            ev = engine.slo.current()
            extra["es.slo.compliant"] = 1 if ev["compliant"] else 0
            extra["es.slo.breached"] = ev["breached_count"]
        except Exception:  # noqa: BLE001 - the scrape must not 500
            pass
        # PR 12 labeled families: the PR-11 host-transition counters by
        # kind, and the compiled-program cost-model drift by kernel
        labeled = {}
        try:
            snap_c = metrics.snapshot()["counters"]
            labeled["es_serving_host_transitions_total"] = {
                "kind": "counter",
                "help": "serving/sharded wave host<->device transitions "
                        "by kind (dispatch = program launches handed to "
                        "the device, fetch = blocking result pulls, "
                        "refresh = refresh-time pack/bitmap uploads — "
                        "the transition budget item 2's background "
                        "DEVICE merges must hold)",
                "samples": [
                    ({"kind": k},
                     snap_c.get(f"es.device.host_transitions.{k}", 0))
                    for k in ("dispatch", "fetch", "refresh")],
            }
            from ..monitoring.xla_introspect import drift_table

            fl, by = [], []
            for kname, row in drift_table().items():
                if "flops_ratio" in row:
                    fl.append(({"kernel": kname}, row["flops_ratio"]))
                    by.append(({"kernel": kname},
                               row.get("bytes_ratio", 0.0)))
            if fl:
                labeled["es_costmodel_drift_flops"] = {
                    "kind": "gauge",
                    "help": "analytic/XLA flops ratio per kernel "
                            "(compiled-program cross-check)",
                    "samples": fl}
                labeled["es_costmodel_drift_bytes"] = {
                    "kind": "gauge",
                    "help": "analytic/XLA bytes-accessed ratio per kernel "
                            "(compiled-program cross-check)",
                    "samples": by}
        except Exception:  # noqa: BLE001 - the scrape must not 500
            labeled = labeled or {}
        # adaptive-planner families (PR 18): decision counts by arm and
        # the predicted-vs-actual |residual| EMA by kernel — the scrape
        # shows WHERE waves are routed and how well the model that
        # routed them tracks reality
        try:
            from ..planner import execution_planner

            pst = execution_planner().stats()
            extra["es.planner.enabled"] = 1 if pst.get("enabled") else 0
            if pst.get("worst_abs_residual_ema") is not None:
                extra["es.planner.worst_abs_residual_ema"] = \
                    pst["worst_abs_residual_ema"]
            if pst.get("decisions"):
                labeled["es_planner_decisions_total"] = {
                    "kind": "counter",
                    "help": "execution-planner arm decisions by arm "
                            "(cost-model argmin routing; cold EMAs fall "
                            "back to the static priority)",
                    "samples": [({"arm": a}, n) for a, n in
                                sorted(pst["decisions"].items())],
                }
            res = [({"kernel": k}, kst["residual_abs_ema"])
                   for k, kst in sorted(pst.get("kernels", {}).items())
                   if "residual_abs_ema" in kst]
            if res:
                labeled["es_planner_residual"] = {
                    "kind": "gauge",
                    "help": "execution-planner |predicted-vs-actual| "
                            "wall residual EMA per kernel (drift in the "
                            "cost model the routing trusts)",
                    "samples": res}
        except Exception:  # noqa: BLE001 - the scrape must not 500
            pass
        # per-tenant families (PR 19): label cardinality is HARD-bounded
        # by the TenantMeter's top-K ledger (overflow folds into the
        # `_other` row) — tenant strings come from the network, so the
        # bound is what keeps a scrape from minting unbounded series;
        # enforced by the cardinality lint in tests/test_tenant_metering
        try:
            if engine._metering is not None:
                rows = engine._metering.rows()
                for fam, key, kind, help_ in (
                        ("es_tenant_device_ms_total", "device_ms",
                         "counter", "exact apportioned device-wall ms "
                         "per tenant (shares sum to each wave's wall)"),
                        ("es_tenant_device_ms_per_s", "device_ms_per_s",
                         "gauge", "per-tenant device-time burn rate "
                         "over the sliding window"),
                        ("es_tenant_requests_total", "requests",
                         "counter", "wave-dispatched requests per "
                         "tenant"),
                        ("es_tenant_sheds_total", "sheds", "counter",
                         "admission-shed (429) requests per tenant"),
                        ("es_tenant_queue_wait_ms_total",
                         "queue_wait_ms", "counter",
                         "cumulative admission-queue wait ms per "
                         "tenant"),
                        ("es_tenant_ingest_bytes_total", "ingest_bytes",
                         "counter", "raw bulk NDJSON bytes per tenant")):
                    samples = [({"tenant": t}, r[key])
                               for t, r in rows.items()]
                    if samples:
                        labeled[fam] = {"kind": kind, "help": help_,
                                        "samples": samples}
        except Exception:  # noqa: BLE001 - the scrape must not 500
            pass
        # ESQL dataflow (PR 20): per-operator cumulative walls as a
        # labeled family — cardinality is hard-bounded by the fixed
        # pipe-stage vocabulary (collect/where/eval/stats_exchange/
        # topn_exchange/... + driver), never by query content
        try:
            est = engine.esql_recorder.stats()
            extra["es.esql.peak_bytes_hwm"] = est.get("peak_bytes_hwm", 0)
            extra["es.esql.breaker_trips"] = est.get("breaker_trips", 0)
            op_samples = [({"operator": k}, v) for k, v in
                          sorted((est.get("operator_ms") or {}).items())]
            if op_samples:
                labeled["es_esql_operator_ms_total"] = {
                    "kind": "counter",
                    "help": "cumulative ESQL per-operator wall ms "
                            "(contiguous segments; per query they sum "
                            "exactly to the query wall)",
                    "samples": op_samples}
        except Exception:  # noqa: BLE001 - the scrape must not 500
            pass
        return web.Response(
            text=metrics.prometheus_text(extra, labeled=labeled),
            content_type="text/plain", charset="utf-8",
        )

    @handler
    async def monitoring_collect(request):
        """POST /_monitoring/_collect: run one collection tick
        synchronously (tests / operators; the interval thread is the
        production path). Works whether or not collection is enabled.
        Runs on the DEFAULT executor, not the engine worker: collect_once
        serializes its engine-touching steps through the worker itself
        (monitoring.submit), so running it there would self-deadlock."""
        loop = asyncio.get_running_loop()
        n = await loop.run_in_executor(None, engine.monitoring.collect_once)
        return web.json_response(
            {"acknowledged": True, "documents": n,
             **engine.monitoring.stats()})

    @handler
    async def monitoring_stats(request):
        return web.json_response(engine.monitoring.stats())

    @handler
    async def monitoring_setup_ml(request):
        """POST /_monitoring/ml/_setup: create the prebuilt self-watch
        anomaly job (datafeed over .monitoring-es-*)."""
        from ..monitoring import setup_self_watch_job

        body = await body_json(request, {}) or {}
        return web.json_response(await call(
            setup_self_watch_job, engine,
            body.get("bucket_span", "15m"), bool(body.get("open", False))))

    @handler
    async def nodes_hot_threads(request):
        """Python-thread analog of _nodes/hot_threads (reference:
        monitor/jvm/HotThreads.java): sample stacks over a short window,
        busiest first — stuck event loop vs device wait at a glance."""
        from ..telemetry import hot_threads_report

        n = int(request.query.get("threads", 3))
        snaps = int(request.query.get("snapshots", 10))
        from ..utils.durations import parse_duration_seconds

        interval = parse_duration_seconds(
            request.query.get("interval"), 0.03) or 0.03
        loop = asyncio.get_running_loop()
        # sampling sleeps — keep it off the event loop (default executor,
        # NOT the single engine worker, which may be what is stuck)
        text = await loop.run_in_executor(
            None, lambda: hot_threads_report(n, snaps, interval))
        return web.Response(text=text, content_type="text/plain")

    app.router.add_get("/", root)
    app.router.add_put("/_ingest/pipeline/{id}", put_pipeline)
    app.router.add_get("/_ingest/pipeline/{id}", get_pipeline)
    app.router.add_get("/_ingest/pipeline", get_pipeline)
    app.router.add_delete("/_ingest/pipeline/{id}", delete_pipeline)
    app.router.add_post("/_ingest/pipeline/{id}/_simulate", simulate_pipeline)
    app.router.add_post("/_ingest/pipeline/_simulate", simulate_pipeline)
    app.router.add_get("/_cluster/health", cluster_health)
    app.router.add_get("/_cluster/health/{index}", cluster_health)
    app.router.add_get("/_cluster/settings", get_cluster_settings)
    app.router.add_put("/_cluster/settings", put_cluster_settings)
    app.router.add_put("/_snapshot/{repo}", put_repository)
    app.router.add_post("/_snapshot/{repo}", put_repository)
    app.router.add_get("/_snapshot", get_repository)
    app.router.add_get("/_snapshot/{repo}", get_repository)
    app.router.add_delete("/_snapshot/{repo}", delete_repository)
    app.router.add_put("/_snapshot/{repo}/{snap}", create_snapshot)
    app.router.add_post("/_snapshot/{repo}/{snap}", create_snapshot)
    app.router.add_get("/_snapshot/{repo}/{snap}", get_snapshot)
    app.router.add_delete("/_snapshot/{repo}/{snap}", delete_snapshot)
    app.router.add_post("/_snapshot/{repo}/{snap}/_restore", restore_snapshot)
    app.router.add_get("/_snapshot/{repo}/{snap}/_status", snapshot_status)
    app.router.add_post("/_snapshot/{repo}/{snap}/_mount", mount_snapshot)
    app.router.add_get("/_searchable_snapshots/cache/stats",
                       searchable_snapshot_cache_stats)
    app.router.add_post("/_aliases", post_aliases)
    app.router.add_get("/_alias", get_alias)
    app.router.add_get("/_alias/{alias}", get_alias, allow_head=False)
    app.router.add_head("/_alias/{alias}", head_alias)
    app.router.add_put("/_index_template/{name}", put_index_template)
    app.router.add_post("/_index_template/{name}", put_index_template)
    app.router.add_get("/_index_template", get_index_template)
    app.router.add_get("/_index_template/{name}", get_index_template, allow_head=False)
    app.router.add_head("/_index_template/{name}", head_index_template)
    app.router.add_delete("/_index_template/{name}", delete_index_template)
    app.router.add_post("/_index_template/_simulate_index/{name}", simulate_index_template)
    app.router.add_put("/_component_template/{name}", put_component_template)
    app.router.add_post("/_component_template/{name}", put_component_template)
    app.router.add_get("/_component_template", get_component_template)
    app.router.add_get("/_component_template/{name}", get_component_template)
    app.router.add_delete("/_component_template/{name}", delete_component_template)
    app.router.add_get("/_cat/indices", cat_indices)
    app.router.add_get("/_nodes/stats", nodes_stats)
    app.router.add_get("/_serving/stats", serving_stats)
    app.router.add_get("/_tenants/stats", tenants_stats)
    app.router.add_get("/_refresh/profile", refresh_profile)
    app.router.add_get("/_esql/profile", esql_profile)
    app.router.add_get("/_serving/flight_recorder", serving_flight_recorder)
    app.router.add_post("/_serving/flight_recorder/_dump",
                        serving_flight_recorder_dump)
    app.router.add_get("/_fault_injection", fault_injection_get)
    app.router.add_post("/_fault_injection", fault_injection_put)
    app.router.add_delete("/_fault_injection", fault_injection_delete)
    app.router.add_post("/_profiler/start", profiler_start)
    app.router.add_post("/_profiler/stop", profiler_stop)
    app.router.add_get("/_profiler", profiler_status)
    app.router.add_get("/_nodes/hot_threads", nodes_hot_threads)
    app.router.add_get("/_trace/{trace_id}", get_trace)
    app.router.add_get("/_prometheus/metrics", prometheus_metrics)
    app.router.add_get("/_monitoring", monitoring_stats)
    app.router.add_post("/_monitoring/_collect", monitoring_collect)
    app.router.add_post("/_monitoring/ml/_setup", monitoring_setup_ml)
    app.router.add_post("/_bulk", bulk)
    app.router.add_post("/_msearch", msearch)
    app.router.add_post("/_search/scroll", scroll_continue)
    app.router.add_get("/_search/scroll", scroll_continue)
    app.router.add_delete("/_search/scroll", scroll_clear)
    app.router.add_post("/_search/scroll/{scroll_id}", scroll_continue)
    app.router.add_delete("/_search/scroll/{scroll_id}", scroll_clear)
    app.router.add_route("*", "/_search", search)
    app.router.add_route("*", "/_count", count)
    app.router.add_delete("/_pit", close_pit)
    app.router.add_post("/_mget", mget)
    app.router.add_get("/_mget", mget)
    app.router.add_route("*", "/_field_caps", field_caps)
    app.router.add_post("/_refresh", refresh_index)

    app.router.add_put("/{index}", create_index)
    app.router.add_delete("/{index}", delete_index)
    app.router.add_get("/{index}", get_index, allow_head=False)
    app.router.add_head("/{index}", head_index)
    app.router.add_get("/{index}/_mapping", get_mapping)
    app.router.add_put("/{index}/_mapping", put_mapping)
    app.router.add_get("/{index}/_settings", get_index_settings)
    app.router.add_put("/{index}/_settings", put_index_settings)
    app.router.add_post("/{index}/_refresh", refresh_index)
    app.router.add_get("/{index}/_refresh", refresh_index)
    app.router.add_post("/{index}/_flush", flush_index)
    app.router.add_post("/{index}/_bulk", bulk)
    app.router.add_route("*", "/{index}/_search", search)
    app.router.add_post("/{index}/_msearch", msearch)
    app.router.add_route("*", "/{index}/_count", count)
    app.router.add_post("/{index}/_doc", put_doc)
    app.router.add_put("/{index}/_doc/{id}", put_doc)
    app.router.add_post("/{index}/_doc/{id}", put_doc)
    app.router.add_get("/{index}/_doc/{id}", get_doc, allow_head=False)
    app.router.add_head("/{index}/_doc/{id}", head_doc)
    app.router.add_delete("/{index}/_doc/{id}", delete_doc)
    app.router.add_put("/{index}/_create/{id}", create_doc)
    app.router.add_post("/{index}/_create/{id}", create_doc)
    app.router.add_get("/{index}/_source/{id}", get_source)
    app.router.add_post("/{index}/_update/{id}", update_doc)
    app.router.add_route("*", "/_search/template", search_template)
    app.router.add_route("*", "/{index}/_search/template", search_template)
    app.router.add_route("*", "/_render/template", render_search_template)
    app.router.add_route("*", "/_render/template/{id}", render_search_template)
    app.router.add_put("/_scripts/{id}", put_stored_script)
    app.router.add_post("/_scripts/{id}", put_stored_script)
    app.router.add_get("/_scripts/{id}", get_stored_script)
    app.router.add_delete("/_scripts/{id}", delete_stored_script)
    app.router.add_route("*", "/{index}/_knn_search", knn_search_api)
    app.router.add_post("/{index}/_graph/explore", graph_explore)
    app.router.add_get("/{index}/_graph/explore", graph_explore)
    app.router.add_put("/_synonyms/{set}", put_synonyms)
    app.router.add_get("/_synonyms", get_synonyms)
    app.router.add_get("/_synonyms/{set}", get_synonyms)
    app.router.add_delete("/_synonyms/{set}", delete_synonyms)
    app.router.add_get("/_recovery", index_recovery)
    app.router.add_get("/{index}/_recovery", index_recovery)
    app.router.add_put("/_template/{name}", legacy_put_template)
    app.router.add_post("/_template/{name}", legacy_put_template)
    app.router.add_get("/_template", legacy_get_template)
    app.router.add_get("/_template/{name}", legacy_get_template)
    app.router.add_delete("/_template/{name}", legacy_delete_template)
    app.router.add_post("/{index}/_close", close_index_api)
    app.router.add_post("/{index}/_open", open_index_api)
    app.router.add_put("/{index}/_block/{block}", add_block_api)
    app.router.add_post("/{index}/_clone/{target}", clone_index_api)
    app.router.add_put("/{index}/_clone/{target}", clone_index_api)
    app.router.add_route("*", "/_msearch/template", msearch_template)
    app.router.add_route("*", "/{index}/_msearch/template", msearch_template)
    app.router.add_route("*", "/_mtermvectors", mtermvectors)
    app.router.add_route("*", "/{index}/_mtermvectors", mtermvectors)
    app.router.add_get("/_cluster/allocation/explain", cluster_allocation_explain)
    app.router.add_post("/_cluster/allocation/explain", cluster_allocation_explain)
    app.router.add_get("/_cluster/pending_tasks", cluster_pending_tasks)
    app.router.add_get("/{index}/_changes", ccr_changes)
    app.router.add_put("/{index}/_ccr/follow", ccr_follow)
    app.router.add_post("/{index}/_ccr/pause_follow", ccr_pause)
    app.router.add_post("/{index}/_ccr/resume_follow", ccr_resume)
    app.router.add_post("/{index}/_ccr/unfollow", ccr_unfollow)
    app.router.add_get("/_ccr/stats", ccr_stats_api)
    app.router.add_put("/_slm/policy/{id}", slm_put)
    app.router.add_get("/_slm/policy", slm_get)
    app.router.add_get("/_slm/policy/{id}", slm_get)
    app.router.add_delete("/_slm/policy/{id}", slm_delete)
    app.router.add_post("/_slm/policy/{id}/_execute", slm_execute_api)
    app.router.add_put("/_watcher/watch/{id}", watcher_put_api)
    app.router.add_post("/_watcher/watch/{id}", watcher_put_api)
    app.router.add_get("/_watcher/watch/{id}", watcher_get_api)
    app.router.add_delete("/_watcher/watch/{id}", watcher_delete_api)
    app.router.add_post("/_watcher/watch/{id}/_execute", watcher_execute_api)
    app.router.add_put("/_watcher/watch/{id}/_ack", watcher_ack_api)
    app.router.add_post("/_watcher/watch/{id}/_ack", watcher_ack_api)
    app.router.add_put("/_watcher/watch/{id}/_ack/{action_id}",
                       watcher_ack_api)
    app.router.add_post("/_watcher/watch/{id}/_ack/{action_id}",
                        watcher_ack_api)
    app.router.add_put("/_watcher/watch/{id}/_activate", watcher_activate_api)
    app.router.add_post("/_watcher/watch/{id}/_activate",
                        watcher_activate_api)
    app.router.add_put("/_watcher/watch/{id}/_deactivate",
                       watcher_deactivate_api)
    app.router.add_post("/_watcher/watch/{id}/_deactivate",
                        watcher_deactivate_api)
    app.router.add_get("/_watcher/stats", watcher_stats_api)
    app.router.add_post("/_watcher/_start", watcher_start_api)
    app.router.add_post("/_watcher/_stop", watcher_stop_api)
    app.router.add_get("/_slo", slo_api)
    app.router.add_put("/_enrich/policy/{name}", enrich_put)
    app.router.add_post("/_enrich/policy/{name}/_execute", enrich_execute)
    app.router.add_get("/_enrich/policy", enrich_get)
    app.router.add_get("/_enrich/policy/{name}", enrich_get)
    app.router.add_delete("/_enrich/policy/{name}", enrich_delete)
    app.router.add_get("/_health_report", health_report_api)
    app.router.add_put("/_ml/anomaly_detectors/{job_id}", ml_put_job)
    app.router.add_get("/_ml/anomaly_detectors", ml_get_jobs)
    app.router.add_get("/_ml/anomaly_detectors/_stats", ml_job_stats)
    app.router.add_get("/_ml/anomaly_detectors/{job_id}", ml_get_jobs)
    app.router.add_delete("/_ml/anomaly_detectors/{job_id}", ml_delete_job)
    app.router.add_post("/_ml/anomaly_detectors/{job_id}/_open", ml_open_job)
    app.router.add_post("/_ml/anomaly_detectors/{job_id}/_close", ml_close_job)
    app.router.add_post("/_ml/anomaly_detectors/{job_id}/_flush", ml_flush_job)
    app.router.add_get("/_ml/anomaly_detectors/{job_id}/_stats", ml_job_stats)
    app.router.add_route(
        "*", "/_ml/anomaly_detectors/{job_id}/results/records", ml_get_records)
    app.router.add_route(
        "*", "/_ml/anomaly_detectors/{job_id}/results/buckets", ml_get_buckets)
    app.router.add_route(
        "*", "/_ml/anomaly_detectors/{job_id}/results/buckets/{timestamp}",
        ml_get_buckets)
    app.router.add_route(
        "*", "/_ml/anomaly_detectors/{job_id}/results/overall_buckets",
        ml_get_overall_buckets)
    app.router.add_get("/_ml/anomaly_detectors/{job_id}/model_snapshots",
                       ml_get_model_snapshots)
    app.router.add_post(
        "/_ml/anomaly_detectors/{job_id}/model_snapshots/{snapshot_id}/_revert",
        ml_revert_model_snapshot)
    app.router.add_put("/_ml/datafeeds/{datafeed_id}", ml_put_datafeed)
    app.router.add_get("/_ml/datafeeds", ml_get_datafeeds)
    app.router.add_get("/_ml/datafeeds/_stats", ml_datafeed_stats)
    app.router.add_get("/_ml/datafeeds/{datafeed_id}", ml_get_datafeeds)
    app.router.add_delete("/_ml/datafeeds/{datafeed_id}", ml_delete_datafeed)
    app.router.add_post("/_ml/datafeeds/{datafeed_id}/_start", ml_start_datafeed)
    app.router.add_post("/_ml/datafeeds/{datafeed_id}/_stop", ml_stop_datafeed)
    app.router.add_get("/_ml/datafeeds/{datafeed_id}/_stats", ml_datafeed_stats)
    app.router.add_get("/_ml/datafeeds/{datafeed_id}/_preview",
                       ml_preview_datafeed)
    app.router.add_post("/_ml/datafeeds/{datafeed_id}/_preview",
                        ml_preview_datafeed)
    app.router.add_get("/_ml/info", ml_info)
    app.router.add_get("/_inference/_all", inference_get)
    app.router.add_get("/_inference/{id}", inference_get)
    app.router.add_put("/_inference/{id}", inference_put)
    app.router.add_delete("/_inference/{id}", inference_delete)
    app.router.add_post("/_inference/{id}", inference_infer)
    app.router.add_put("/_inference/{task_type}/{id}", inference_put)
    app.router.add_get("/_inference/{task_type}/{id}", inference_get)
    app.router.add_delete("/_inference/{task_type}/{id}", inference_delete)
    app.router.add_post("/_inference/{task_type}/{id}", inference_infer)
    app.router.add_put("/_transform/{id}", transform_put)
    app.router.add_get("/_transform", transform_get)
    app.router.add_get("/_transform/{id}", transform_get)
    app.router.add_get("/_transform/{id}/_stats", transform_stats)
    app.router.add_delete("/_transform/{id}", transform_delete)
    app.router.add_post("/_transform/{id}/_start", transform_start)
    app.router.add_post("/_transform/{id}/_stop", transform_stop)
    app.router.add_post("/_transform/_preview", transform_preview)
    app.router.add_post("/{index}/_downsample/{target}", downsample_api)
    app.router.add_get("/_remote/info", remote_info)
    app.router.add_get("/_security/_authenticate", security_authenticate)
    app.router.add_put("/_security/user/{name}", security_put_user)
    app.router.add_post("/_security/user/{name}", security_put_user)
    app.router.add_get("/_security/user", security_get_user)
    app.router.add_get("/_security/user/{name}", security_get_user)
    app.router.add_delete("/_security/user/{name}", security_delete_user)
    app.router.add_post("/_security/user/{name}/_password", security_change_password)
    app.router.add_post("/_security/user/_password", security_change_password)
    app.router.add_put("/_security/role/{name}", security_put_role)
    app.router.add_post("/_security/role/{name}", security_put_role)
    app.router.add_get("/_security/role", security_get_role)
    app.router.add_get("/_security/role/{name}", security_get_role)
    app.router.add_delete("/_security/role/{name}", security_delete_role)
    app.router.add_post("/_security/api_key", security_create_api_key)
    app.router.add_put("/_security/api_key", security_create_api_key)
    app.router.add_get("/_security/api_key", security_get_api_keys)
    app.router.add_delete("/_security/api_key", security_invalidate_api_key)
    app.router.add_post("/_query", esql_api)
    app.router.add_post("/_esql/query", esql_api)
    app.router.add_post("/_sql", sql_api)
    app.router.add_route("*", "/{index}/_eql/search", eql_api)
    app.router.add_post("/_async_search", submit_async_search)
    app.router.add_post("/{index}/_async_search", submit_async_search)
    app.router.add_get("/_async_search/status/{id}", get_async_search_status)
    app.router.add_get("/_async_search/{id}", get_async_search)
    app.router.add_delete("/_async_search/{id}", delete_async_search)
    app.router.add_put("/_data_stream/{name}", put_data_stream)
    app.router.add_get("/_data_stream", get_data_stream)
    app.router.add_get("/_data_stream/{name}", get_data_stream)
    app.router.add_delete("/_data_stream/{name}", delete_data_stream)
    app.router.add_post("/{target}/_rollover", rollover_api)
    app.router.add_post("/{target}/_rollover/{new_index}", rollover_api)
    app.router.add_put("/_ilm/policy/{name}", ilm_put_policy)
    app.router.add_get("/_ilm/policy", ilm_get_policy)
    app.router.add_get("/_ilm/policy/{name}", ilm_get_policy)
    app.router.add_delete("/_ilm/policy/{name}", ilm_delete_policy)
    app.router.add_get("/{index}/_ilm/explain", ilm_explain)
    app.router.add_route("*", "/_rank_eval", rank_eval_api)
    app.router.add_route("*", "/{index}/_rank_eval", rank_eval_api)
    app.router.add_route("*", "/_analyze", analyze_api)
    app.router.add_route("*", "/{index}/_analyze", analyze_api)
    app.router.add_route("*", "/_validate/query", validate_query_api)
    app.router.add_route("*", "/{index}/_validate/query", validate_query_api)
    app.router.add_route("*", "/{index}/_termvectors/{id}", termvectors_api)
    app.router.add_get("/_stats", index_stats_api)
    app.router.add_get("/{index}/_stats", index_stats_api)
    app.router.add_get("/_segments", index_segments_api)
    app.router.add_get("/{index}/_segments", index_segments_api)
    app.router.add_get("/_cluster/state", cluster_state_api)
    app.router.add_get("/_cluster/state/{metrics}", cluster_state_api)
    app.router.add_get("/_cluster/stats", cluster_stats_api)
    app.router.add_get("/_nodes", nodes_info_api)
    app.router.add_get("/_resolve/index/{name}", resolve_index_api)
    app.router.add_get("/_cat/health", cat_health_api)
    app.router.add_get("/_cat/nodes", cat_nodes_api)
    app.router.add_get("/_cat/count", cat_count_api)
    app.router.add_get("/_cat/count/{index}", cat_count_api)
    app.router.add_get("/_cat/shards", cat_shards_api)
    app.router.add_get("/_cat/shards/{index}", cat_shards_api)
    app.router.add_get("/_cat/aliases", cat_aliases_api)
    app.router.add_get("/_cat/allocation", cat_allocation_api)
    app.router.add_get("/_cat/master", cat_master_api)
    app.router.add_get("/_cat/recovery", cat_recovery_api)
    app.router.add_get("/_cat/plugins", cat_plugins_api)
    app.router.add_get("/_cat/templates", cat_templates_api)
    app.router.add_get("/_cat/tasks", cat_tasks_api)
    app.router.add_get("/_cat/tenants", cat_tenants_api)
    app.router.add_get("/_tasks", tasks_list)
    app.router.add_get("/_tasks/{task_id}", tasks_get)
    app.router.add_post("/_tasks/_cancel", tasks_cancel)
    app.router.add_post("/_tasks/{task_id}/_cancel", tasks_cancel)
    app.router.add_post("/{index}/_update_by_query", update_by_query)
    app.router.add_post("/{index}/_delete_by_query", delete_by_query)
    app.router.add_post("/_reindex", reindex)
    app.router.add_put("/{index}/_alias/{alias}", put_alias)
    app.router.add_post("/{index}/_alias/{alias}", put_alias)
    app.router.add_put("/{index}/_aliases/{alias}", put_alias)
    app.router.add_delete("/{index}/_alias/{alias}", delete_alias)
    app.router.add_delete("/{index}/_aliases/{alias}", delete_alias)
    app.router.add_get("/{index}/_alias", get_alias)
    app.router.add_get("/{index}/_alias/{alias}", get_alias, allow_head=False)
    app.router.add_head("/{index}/_alias/{alias}", head_alias)
    app.router.add_post("/{index}/_mget", mget)
    app.router.add_get("/{index}/_mget", mget)
    app.router.add_route("*", "/{index}/_explain/{id}", explain_doc)
    app.router.add_route("*", "/{index}/_field_caps", field_caps)
    app.router.add_post("/{index}/_pit", open_pit)

    # plugin-contributed REST handlers (ActionPlugin#getRestHandlers):
    # wrapped in the same error envelope as built-in routes
    from ..plugins import registry as _plugin_registry

    for method, path, h in _plugin_registry.rest_handlers:
        app.router.add_route(method, path, handler(h))

    async def on_cleanup(app):
        # serving first: its wave stages run ON the pool, so the pool
        # must still be alive while in-flight waves drain
        if engine._serving is not None:
            engine._serving.stop()
        app["pool"].shutdown(wait=True)
        engine.close()

    app.on_cleanup.append(on_cleanup)
    return app
