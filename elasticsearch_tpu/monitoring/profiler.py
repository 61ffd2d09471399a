"""Duration-bounded device profiling: the `jax.profiler` trace surface.

The reference ships Universal Profiling (whole-fleet eBPF) as a
stand-alone x-pack stack; this engine's profiler of record is the XLA
runtime's own: `jax.profiler.start_trace/stop_trace` writes an XPlane
protobuf trace (TensorBoard/XProf-readable) containing every device
kernel launch, transfer, and host callback of the window. This module
wraps it as a node service so that

  - operators can start/stop a capture over REST
    (`POST /_profiler/{start,stop}`, `GET /_profiler`);
  - the watcher `capture` action can take a bounded trace when an SLO
    objective breaches (evidence, not just an alert doc);
  - every capture is DURATION-BOUNDED (`xpack.profiling.max_duration`
    clamps requests; a watchdog timer force-stops a forgotten trace), and
  - the trace directory is retention-pruned by the monitoring
    CleanerService (`xpack.profiling.retention`) like the dated hidden
    indices — a breach storm cannot fill the disk.

Only one trace can be active per process (an XLA constraint); concurrent
start/capture requests get a structured refusal, never a crash.
"""

from __future__ import annotations

import os
import shutil
import threading
import time

from ..telemetry import annotate_stages, log, metrics

CAPTURE_PREFIX = "capture-"
# the host tracer level that keeps TraceAnnotations and PJRT's TraceMes
# (the library's default)
HOST_TRACER_LEVEL = 2

# the XLA profiler is a PROCESS singleton: multiple engines in one
# process (cluster test fixtures, embedded nodes) must share one lock
# and one active-trace slot, or a second engine's start corrupts the
# first engine's capture
_GLOBAL_LOCK = threading.Lock()


class _Shared:
    """Process-global active-trace slot (shared by every engine)."""

    active: dict | None = None
    watchdog: threading.Timer | None = None


class ProfilerService:
    """Per-engine bounded jax.profiler trace capture."""

    def __init__(self, engine):
        self.engine = engine
        self._lock = _GLOBAL_LOCK
        self.captures_total = 0
        self.last_capture: dict | None = None

    @property
    def _active(self):
        return _Shared.active

    # -- settings ----------------------------------------------------------

    def _get(self, key, default=None):
        try:
            v = self.engine.settings.get(key)
        except Exception:  # noqa: BLE001 - engines without the setting
            return default
        return default if v is None else v

    @property
    def enabled(self) -> bool:
        return bool(self._get("xpack.profiling.enabled", True))

    def max_duration_s(self) -> float:
        from ..utils.durations import parse_duration_seconds

        raw = self._get("xpack.profiling.max_duration", "10s")
        return max(parse_duration_seconds(raw, 10.0) or 10.0, 0.05)

    def retention_s(self) -> float:
        from ..utils.durations import parse_duration_seconds

        raw = self._get("xpack.profiling.retention", "1h")
        return max(parse_duration_seconds(raw, 3600.0) or 3600.0, 1.0)

    def trace_dir(self) -> str:
        configured = str(self._get("xpack.profiling.trace_dir", "") or "")
        if configured:
            return configured
        data = getattr(self.engine, "data_path", None)
        if data:
            return os.path.join(data, "profiler")
        import tempfile

        return os.path.join(tempfile.gettempdir(),
                            f"elasticsearch-tpu-profiler-{os.getpid()}")

    # -- trace lifecycle ---------------------------------------------------

    def start(self, duration_s: float | None = None,
              reason: str = "manual") -> dict:
        """Start a trace into a fresh capture dir. duration_s (clamped to
        xpack.profiling.max_duration) arms the watchdog that force-stops
        the trace — an operator who forgets `stop` cannot leave the
        profiler running across a serving day.

        The capture holds the device's operations, PJRT's own host events
        and the stage annotations of telemetry.ANNOTATED_STAGES, and no
        Python frames: the Python tracer hooks every call and slowed the
        host it measured by two fifths."""
        if not self.enabled:
            return {"started": False, "reason": "xpack.profiling.enabled "
                                                "is false"}
        bound = self.max_duration_s()
        dur = min(duration_s, bound) if duration_s else bound
        with self._lock:
            if _Shared.active is not None:
                return {"started": False, "reason": "trace already active",
                        "active": self._status_locked()}
            cap_dir = os.path.join(
                self.trace_dir(), f"{CAPTURE_PREFIX}{int(time.time() * 1000)}")
            os.makedirs(cap_dir, exist_ok=True)
            try:
                import jax.profiler

                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = HOST_TRACER_LEVEL
                jax.profiler.start_trace(cap_dir, profiler_options=options)
            except Exception as e:  # noqa: BLE001 - backend w/o profiler
                return {"started": False,
                        "reason": f"{type(e).__name__}: {e}"}
            annotate_stages(True)
            _Shared.active = {"dir": cap_dir,
                              "started_unix": time.time(),
                              "bound_s": dur, "trigger": reason,
                              "owner": id(self)}
            _Shared.watchdog = threading.Timer(
                dur, self.stop, kwargs={"_watchdog": True})
            _Shared.watchdog.daemon = True
            _Shared.watchdog.start()
            metrics.counter_inc("es.profiler.traces_started")
            return {"started": True, "dir": cap_dir, "bound_s": dur,
                    "trigger": reason}

    def stop(self, _watchdog: bool = False) -> dict:
        with self._lock:
            active = _Shared.active
            if active is None:
                return {"stopped": False, "reason": "no active trace"}
            _Shared.active = None
            annotate_stages(False)
            if _Shared.watchdog is not None:
                _Shared.watchdog.cancel()
                _Shared.watchdog = None
            try:
                import jax.profiler

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                log.debug("profiler stop failed: %s", e)
            files = []
            nbytes = 0
            for root, _dirs, names in os.walk(active["dir"]):
                for nm in names:
                    p = os.path.join(root, nm)
                    try:
                        nbytes += os.path.getsize(p)
                    except OSError:
                        continue
                    files.append(os.path.relpath(p, active["dir"]))
            out = {
                "stopped": True,
                "dir": active["dir"],
                "trigger": active["trigger"],
                "duration_ms": round(
                    (time.time() - active["started_unix"]) * 1000, 3),
                "by_watchdog": _watchdog,
                "files": sorted(files),
                "bytes": nbytes,
            }
            self.captures_total += 1
            self.last_capture = out
            metrics.counter_inc("es.profiler.traces_completed")
            return out

    def capture(self, duration_s: float | None = None,
                reason: str = "breach") -> dict:
        """Synchronous bounded capture (the watcher action): start, hold
        the window open (a tiny device op guarantees the trace is never
        empty of device activity), stop. Refuses politely if a trace is
        already running."""
        dur = min(duration_s or 0.2, self.max_duration_s())
        started = self.start(duration_s=max(dur * 4, 1.0), reason=reason)
        if not started.get("started"):
            return started
        try:
            import jax
            import jax.numpy as jnp

            x = jnp.ones((128, 128), jnp.float32)
            jax.block_until_ready(x @ x)
            time.sleep(dur)
        except Exception:  # noqa: BLE001 - the stop below still runs
            pass
        return self.stop()

    # -- introspection / retention ----------------------------------------

    def _status_locked(self) -> dict:
        a = _Shared.active
        return {"active": a is not None,
                **({"dir": a["dir"], "trigger": a["trigger"],
                    "running_ms": round(
                        (time.time() - a["started_unix"]) * 1000, 1)}
                   if a is not None else {})}

    def status(self) -> dict:
        with self._lock:
            st = self._status_locked()
        st.update({
            "enabled": self.enabled,
            "trace_dir": self.trace_dir(),
            "max_duration_s": self.max_duration_s(),
            "retention_s": self.retention_s(),
            "captures_total": self.captures_total,
            "last_capture": self.last_capture,
            "retained_captures": self.list_captures(),
        })
        return st

    def list_captures(self) -> list[str]:
        base = self.trace_dir()
        try:
            return sorted(d for d in os.listdir(base)
                          if d.startswith(CAPTURE_PREFIX))
        except OSError:
            return []

    def prune(self) -> list[str]:
        """Delete capture dirs older than xpack.profiling.retention.
        Called by the monitoring CleanerService pass alongside the dated
        hidden indices; the active capture is never pruned."""
        base = self.trace_dir()
        cutoff_ms = (time.time() - self.retention_s()) * 1000
        with self._lock:
            active_dir = (_Shared.active["dir"]
                          if _Shared.active else None)
        pruned = []
        for d in self.list_captures():
            full = os.path.join(base, d)
            if full == active_dir:
                continue
            try:
                stamp = float(d[len(CAPTURE_PREFIX):])
            except ValueError:
                continue
            if stamp < cutoff_ms:
                shutil.rmtree(full, ignore_errors=True)
                pruned.append(d)
        if pruned:
            metrics.counter_inc("es.profiler.captures_pruned", len(pruned))
        return pruned

    def close(self) -> None:
        # only stop a trace THIS engine started — in multi-engine
        # processes (cluster fixtures) closing one engine must not kill
        # another engine's in-flight capture
        with self._lock:
            owned = (_Shared.active is not None
                     and _Shared.active.get("owner") == id(self))
        if owned:
            self.stop()
