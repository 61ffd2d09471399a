#!/usr/bin/env python
"""Pretty-print a stored trace as a time-aligned tree.

Source:
  --url http://host:port --trace <trace_id>   fetch GET /_trace/{id} from a
                                              node or cluster gateway

Output: one line per span, indented by depth, with a time-aligned bar over
the trace's wall-clock window, the owning node, and duration — enough to
see at a glance whether tail latency sat in the coordinator, a shard's
pack build, or the device.

    $ python scripts/trace_dump.py --url http://127.0.0.1:9200 \
          --trace 4bf92f3577b34da6a3ce929d0e0e4736

Dependency-free (urllib only), like scripts/tcp_cluster_demo.py.
"""

from __future__ import annotations

import argparse
import json
import sys

BAR_WIDTH = 40


def _fetch_url(url: str, trace_id: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(
            f"{url.rstrip('/')}/_trace/{trace_id}", timeout=30.0) as r:
        return json.loads(r.read())


def _window(roots: list[dict]) -> tuple[float, float]:
    lo, hi = float("inf"), float("-inf")

    def visit(s):
        nonlocal lo, hi
        lo = min(lo, s["start_unix"])
        hi = max(hi, s["start_unix"] + s["duration_ms"] / 1000.0)
        for c in s.get("children", []):
            visit(c)

    for r in roots:
        visit(r)
    return lo, max(hi, lo + 1e-9)


def _bar(start: float, dur_ms: float, lo: float, span_s: float) -> str:
    a = int(BAR_WIDTH * (start - lo) / span_s)
    b = int(BAR_WIDTH * (start - lo + dur_ms / 1000.0) / span_s)
    b = max(b, a + 1)
    return "·" * a + "█" * (b - a) + "·" * max(BAR_WIDTH - b, 0)


def render(trace: dict, out=None) -> None:
    out = out or sys.stdout  # late-bound: an import-time stdout may be a closed capture
    roots = trace.get("spans", [])
    lo, hi = _window(roots)
    span_s = hi - lo
    print(f"trace {trace.get('trace_id')}  "
          f"spans={trace.get('span_count', len(roots))}  "
          f"nodes={','.join(trace.get('nodes', []))}  "
          f"window={span_s * 1000:.1f}ms", file=out)

    def visit(s, depth):
        attrs = " ".join(
            f"{k}={v}" for k, v in sorted((s.get("attributes") or {}).items())
        )
        print(f"  [{_bar(s['start_unix'], s['duration_ms'], lo, span_s)}] "
              f"{'  ' * depth}{s['name']}  "
              f"({s['duration_ms']:.2f}ms, node={s['node']}"
              f"{', ' + attrs if attrs else ''})", file=out)
        for c in sorted(s.get("children", []),
                        key=lambda c: c.get("start_unix", 0.0)):
            visit(c, depth + 1)

    for r in sorted(roots, key=lambda s: s.get("start_unix", 0.0)):
        visit(r, 0)


# ---------------------------------------------------------------------------
# flight-recorder rendering (PR 12)
# ---------------------------------------------------------------------------

_SEG_ORDER = ("queue", "plan", "device", "finish")
_SEG_CHARS = {"queue": "░", "plan": "▒", "device": "█", "finish": "▓"}


def _fetch_flight(url: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(
            f"{url.rstrip('/')}/_serving/flight_recorder", timeout=30.0) as r:
        return json.loads(r.read())


def _load_flight(path: str) -> dict:
    """A saved GET /_serving/flight_recorder body, or a JSON-lines dump
    of `.flight-recorder-*` docs (one wave record per line)."""
    with open(path, encoding="utf-8") as fh:
        head = fh.read(1)
        fh.seek(0)
        if head == "{":
            try:
                return json.load(fh)
            except json.JSONDecodeError:
                fh.seek(0)
        waves = [json.loads(ln) for ln in fh if ln.strip()]
    return {"capacity": None, "retained": len(waves), "waves": waves}


def render_flight(snap: dict, out=None) -> None:
    """One line per recorded wave: a BAR_WIDTH bar partitioned by the
    wave's segment timings (queue ░ / plan ▒ / device █ / finish ▓ —
    contiguous, summing to the wall time), plus size/tenant/kernel
    attribution. The per-wave analog of the span tree above: where did
    this wave's wall time actually sit."""
    out = out or sys.stdout
    waves = snap.get("waves", [])
    print(f"flight recorder: {len(waves)} wave(s) retained "
          f"(capacity={snap.get('capacity')}, "
          f"recorded_total={snap.get('recorded_total')})", file=out)
    legend = "  ".join(f"{_SEG_CHARS[s]} {s}" for s in _SEG_ORDER)
    print(f"  segments: {legend}", file=out)
    for w in waves:
        seg = w.get("segments_ms") or {}
        wall = max(float(w.get("wall_ms") or 0.0), 1e-9)
        bar = ""
        for s in _SEG_ORDER:
            n = int(round(BAR_WIDTH * float(seg.get(s, 0.0)) / wall))
            bar += _SEG_CHARS[s] * n
        bar = (bar + "·" * BAR_WIDTH)[:BAR_WIDTH]
        tr = w.get("host_transitions") or {}
        kernels = w.get("kernels") or {}
        top_kernel = max(kernels, key=lambda k: kernels[k].get("ms", 0.0),
                         default=None)
        extras = []
        if top_kernel:
            tk = kernels[top_kernel]
            extras.append(f"top={top_kernel}:{tk.get('ms', 0)}ms"
                          f" mfu={tk.get('mfu', 0)}")
        if w.get("escalations"):
            extras.append(f"esc={w['escalations']}")
        # planner decision attribution (PR 18): chosen arm + mode, the
        # predicted wall next to what the dispatch actually cost
        for d in (w.get("decisions") or []):
            pred = (d.get("predicted_ms") or {}).get(d.get("arm"))
            col = f"plan={d.get('arm')}[{d.get('mode')}]"
            if pred is not None:
                col += f" pred={pred}ms"
            if d.get("actual_ms") is not None:
                col += f" act={d['actual_ms']}ms"
            if d.get("residual") is not None:
                col += f" res={d['residual']:+}"
            extras.append(col)
        if w.get("error"):
            extras.append("ERROR")
        print(f"  [{bar}] w{w.get('wave'):>4} size={w.get('size'):>3} "
              f"wall={wall:8.2f}ms "
              f"q/p/d/f={seg.get('queue', 0):.1f}/{seg.get('plan', 0):.1f}"
              f"/{seg.get('device', 0):.1f}/{seg.get('finish', 0):.1f} "
              f"tr={tr.get('dispatch', 0)}+{tr.get('fetch', 0)} "
              f"tenants={len(w.get('tenants') or {})}"
              f"{' ' + ' '.join(extras) if extras else ''}", file=out)
        # per-tenant apportionment bar (PR 19): one sub-line per multi-
        # tenant wave, partitioning the wave's DEVICE segment by each
        # tenant's exact apportioned share (the shares sum to the device
        # wall by construction, so the bar covers the segment exactly)
        mix = w.get("tenants") or {}
        if len(mix) > 1 and isinstance(next(iter(mix.values())), dict):
            dev = max(float(seg.get("device", 0.0)), 1e-9)
            tbar, parts = "", []
            glyphs = "▆▄▂▇▅▃▁"
            order = sorted(mix, key=lambda t: -mix[t].get("device_ms", 0.0))
            for i, t in enumerate(order):
                share = float(mix[t].get("device_ms", 0.0))
                g = glyphs[i % len(glyphs)]
                tbar += g * int(round(BAR_WIDTH * share / dev))
                parts.append(f"{g} {t}={share:.2f}ms")
            tbar = (tbar + "·" * BAR_WIDTH)[:BAR_WIDTH]
            print(f"  [{tbar}] device split: {'  '.join(parts)}",
                  file=out)


# ---------------------------------------------------------------------------
# refresh-profile rendering (PR 13)
# ---------------------------------------------------------------------------

# stages get bar glyphs in first-seen order; the build.* kernels come
# first so the same stage keeps the same glyph across refreshes
_REFRESH_SEED_STAGES = ("build.kmeans", "build.impact_quantize",
                        "build.csr_assemble", "build.norms",
                        "build.ann_tiles", "build.device_put",
                        "build.merge", "analyze", "host_other")
# NOTE: "·" is reserved for bar padding, never a stage glyph
_REFRESH_GLYPHS = "█▓▒░▞▚◆●○◇•▪▫≋"


def _fetch_refresh(url: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(
            f"{url.rstrip('/')}/_refresh/profile", timeout=30.0) as r:
        return json.loads(r.read())


def _load_refresh(path: str) -> dict:
    """A saved GET /_refresh/profile body, or JSON lines of RefreshProfile
    records (one per line)."""
    with open(path, encoding="utf-8") as fh:
        head = fh.read(1)
        fh.seek(0)
        if head == "{":
            try:
                body = json.load(fh)
                if "profiles" in body:
                    return body
                return {"capacity": None, "retained": 1,
                        "profiles": [body]}
            except json.JSONDecodeError:
                fh.seek(0)
        profs = [json.loads(ln) for ln in fh if ln.strip()]
    return {"capacity": None, "retained": len(profs), "profiles": profs}


def render_refresh(snap: dict, out=None) -> None:
    """One line per recorded refresh: a BAR_WIDTH bar partitioned by the
    contiguous build-stage timings (they sum to the wall time by
    construction — monitoring/refresh_profile), plus kind / docs /
    tail_fraction — the per-refresh analog of --flight's per-wave bar:
    where did this refresh's wall time actually sit."""
    out = out or sys.stdout
    profs = snap.get("profiles", [])
    print(f"refresh profiles: {len(profs)} refresh(es) retained "
          f"(capacity={snap.get('capacity')}, "
          f"recorded_total={snap.get('recorded_total')})", file=out)
    glyph_of: dict[str, str] = {}

    def glyph(stage: str) -> str:
        if stage not in glyph_of:
            glyph_of[stage] = _REFRESH_GLYPHS[
                len(glyph_of) % len(_REFRESH_GLYPHS)]
        return glyph_of[stage]

    for s in _REFRESH_SEED_STAGES:
        glyph(s)
    for p in profs:
        seg = p.get("stages_ms") or {}
        wall = max(float(p.get("wall_ms") or 0.0), 1e-9)
        bar = ""
        for stage in sorted(seg, key=seg.get, reverse=True):
            n = int(round(BAR_WIDTH * float(seg[stage]) / wall))
            bar += glyph(stage) * n
        bar = (bar + "·" * BAR_WIDTH)[:BAR_WIDTH]
        top = max(seg, key=seg.get, default=None)
        tiers = p.get("tiers") or {}
        print(f"  [{bar}] r{p.get('refresh'):>4} "
              f"{(p.get('kind') or '?'):<11} "
              f"idx={p.get('index')} docs={p.get('docs'):>6} "
              f"wall={wall:9.2f}ms "
              f"tail={p.get('tail_fraction', 0):.4f} "
              f"(base={tiers.get('base_docs', 0)}"
              f"+tail={tiers.get('tail_docs', 0)})"
              f"{f'  top={top}:{seg[top]:.1f}ms' if top else ''}",
              file=out)
    used = [s for s in glyph_of if any(s in (p.get("stages_ms") or {})
                                       for p in profs)]
    if used:
        print("  stages: " + "  ".join(f"{glyph_of[s]} {s}"
                                       for s in used), file=out)


def _fetch_esql(url: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(
            f"{url.rstrip('/')}/_esql/profile", timeout=30.0) as r:
        return json.loads(r.read())


def _load_esql(path: str) -> dict:
    """A saved GET /_esql/profile body, a single profile body (e.g. the
    `profile` section of a POST /_query response), or JSON lines of
    profile records — including dumped monitoring TSDB docs, whose
    node_stats.esql sections are skipped (they carry cumulative stats,
    not per-query operator walls)."""
    with open(path, encoding="utf-8") as fh:
        head = fh.read(1)
        fh.seek(0)
        if head == "{":
            try:
                body = json.load(fh)
                if "profiles" in body:
                    return body
                if "drivers" in body.get("profile", {}):
                    return {"capacity": None, "retained": 1,
                            "profiles": [body["profile"]]}
                return {"capacity": None, "retained": 1,
                        "profiles": [body]}
            except json.JSONDecodeError:
                fh.seek(0)
        profs = []
        for ln in fh:
            if not ln.strip():
                continue
            rec = json.loads(ln)
            src = rec.get("_source", rec)
            if "drivers" in src:
                profs.append(src)
    return {"capacity": None, "retained": len(profs), "profiles": profs}


# seed the stable glyph order with the fixed pipe-stage vocabulary so
# the same operator renders the same glyph across queries (the
# --refresh convention)
_ESQL_SEED_OPS = ("collect", "where", "eval", "stats_exchange", "stats",
                  "topn_exchange", "sort", "limit", "keep", "driver")


def render_esql(snap: dict, out=None) -> None:
    """One line per recorded ESQL query: a BAR_WIDTH bar partitioned by
    the contiguous per-operator walls (they sum to the query wall
    EXACTLY — esql/profile.py), plus rows / peak live bytes / dominant
    operator — the per-query analog of --refresh's per-refresh bar:
    where did this query's wall time actually sit (PR 20)."""
    out = out or sys.stdout
    profs = snap.get("profiles", [])
    ring = ""
    if snap.get("capacity") is not None:
        ring = (f" (capacity={snap.get('capacity')}, "
                f"recorded_total={snap.get('recorded_total')})")
    print(f"esql profiles: {len(profs)} quer(ies) retained{ring}",
          file=out)
    glyph_of: dict[str, str] = {}

    def glyph(op: str) -> str:
        if op not in glyph_of:
            glyph_of[op] = _REFRESH_GLYPHS[
                len(glyph_of) % len(_REFRESH_GLYPHS)]
        return glyph_of[op]

    for s in _ESQL_SEED_OPS:
        glyph(s)
    seen_ops: set = set()
    for p in profs:
        ops = (p.get("drivers") or [{}])[0].get("operators") or []
        seg = {o["operator"]: float(o.get("took_ms", 0.0)) for o in ops}
        seen_ops |= set(seg)
        wall = max(float(p.get("wall_ms") or 0.0), 1e-9)
        bar = ""
        for op in seg:  # insertion order == pipeline order (contiguous)
            n = int(round(BAR_WIDTH * seg[op] / wall))
            bar += glyph(op) * n
        bar = (bar + "·" * BAR_WIDTH)[:BAR_WIDTH]
        top = max(seg, key=seg.get, default=None)
        q = str(p.get("query") or "?").replace("\n", " ")
        print(f"  [{bar}] q{p.get('seq', '?'):>4} "
              f"rows={p.get('rows', 0):>6} "
              f"wall={wall:9.2f}ms "
              f"peak={p.get('peak_live_bytes', 0):>10}b "
              f"dom={p.get('dominant_operator') or '?'}"
              f"{f'  top={top}:{seg[top]:.1f}ms' if top else ''}"
              f"  | {q[:60]}",
              file=out)
    used = [s for s in glyph_of if s in seen_ops]
    if used:
        print("  operators: " + "  ".join(f"{glyph_of[s]} {s}"
                                          for s in used), file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", help="node/gateway base URL to fetch from")
    ap.add_argument("--trace", help="trace id (32 hex)")
    ap.add_argument("--flight", nargs="?", const="-",
                    help="render the serving flight recorder instead of a "
                         "trace: with a PATH, read a saved recorder body "
                         "or a JSON-lines dump; bare --flight fetches "
                         "GET /_serving/flight_recorder from --url")
    ap.add_argument("--refresh", nargs="?", const="-",
                    help="render the write-path refresh profiles instead "
                         "of a trace: with a PATH, read a saved "
                         "GET /_refresh/profile body or JSON-lines "
                         "RefreshProfile records; bare --refresh fetches "
                         "from --url (PR 13)")
    ap.add_argument("--esql", nargs="?", const="-",
                    help="render the per-query ESQL operator profiles "
                         "instead of a trace: with a PATH, read a saved "
                         "GET /_esql/profile body, a POST /_query "
                         "profile section, or JSON-lines profile "
                         "records (TSDB dumps included); bare --esql "
                         "fetches from --url (PR 20)")
    args = ap.parse_args(argv)
    if args.esql is not None:
        if args.esql == "-":
            if not args.url:
                ap.error("bare --esql needs --url to fetch from")
            snap = _fetch_esql(args.url)
        else:
            snap = _load_esql(args.esql)
        if not snap.get("profiles"):
            print("esql profiles: none recorded", file=sys.stderr)
            return 1
        render_esql(snap)
        return 0
    if args.refresh is not None:
        if args.refresh == "-":
            if not args.url:
                ap.error("bare --refresh needs --url to fetch from")
            snap = _fetch_refresh(args.url)
        else:
            snap = _load_refresh(args.refresh)
        if not snap.get("profiles"):
            print("refresh profiles: none recorded", file=sys.stderr)
            return 1
        render_refresh(snap)
        return 0
    if args.flight is not None:
        if args.flight == "-":
            if not args.url:
                ap.error("bare --flight needs --url to fetch from")
            snap = _fetch_flight(args.url)
        else:
            snap = _load_flight(args.flight)
        if not snap.get("waves"):
            print("flight recorder: no waves recorded", file=sys.stderr)
            return 1
        render_flight(snap)
        return 0
    if not args.trace:
        ap.error("--trace is required (or use --flight / --refresh / "
                 "--esql)")
    if not args.url:
        ap.error("--url is required with --trace")
    trace = _fetch_url(args.url, args.trace)
    if not trace.get("spans"):
        print(f"trace {args.trace}: no spans found", file=sys.stderr)
        return 1
    render(trace)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
