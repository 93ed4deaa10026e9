"""Run-scoped telemetry: the measurement layer under every BENCH entry.

One :class:`RunTelemetry` object lives for the duration of a pipeline
run (`PeasoupSearch.run`, the FFA search, the coincidencer). It
collects:

- **stage timers and spans** — per-stage wall time; the keys mirror
  the ``<execution_times>`` table in overview.xml. Every
  :meth:`RunTelemetry.stage` is also a span: stamped with
  ``time.time_ns()`` (the clock of JAX profiler traces), recorded with
  its parent in the manifest's ``spans`` list, and mirrored into any
  running profiler trace as a ``peasoup.<name>`` annotation,
- **counters / gauges** — trial counts, candidate counts per stage,
  per-device memory high-water marks (``device.memory_stats()`` where
  the backend reports them),
- **events** — every adaptive decision the driver takes (OOM
  shrink-retry with old/new ``dm_block``, Pallas-disable fallback,
  peak-compaction escalation, wave/chunk geometry, checkpoint resume)
  as structured records with a monotonic offset, replacing bare
  warnings that used to vanish with the terminal scrollback,
- **JIT stats** — compile/lowering counts and durations via
  ``jax.monitoring`` listeners,
- **device trace** (opt-in, ``--capture-device-trace``) — per-scope
  device-time and bytes-accessed attribution folded in from
  ``tools/scope_trace.py``'s profiler parsing.

The result serialises to a versioned ``telemetry.json`` run manifest
(written next to overview.xml by the `peasoup` CLI); render or diff
manifests with ``python -m peasoup_tpu.tools.report``.

Propagation is ambient: the driver calls :func:`current` to get the
run's telemetry (activated by the CLI via ``RunTelemetry.activate``),
so deep pipeline code records events without threading the object
through every signature. When nothing is active, :data:`NOOP` absorbs
every call at near-zero cost — library users who never asked for
telemetry pay nothing and no file is written.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import socket
import sys
import threading
import time

MANIFEST_SCHEMA = "peasoup_tpu.telemetry"
# v2: top-level process_index/process_count (per-host shard tagging for
# tools/report.py --merge) and the optional aborted/abort_reason pair
# written by the crash flight recorder (obs/flight.py). Readers must
# .get() keys newer than a manifest's version — see tools/report.py.
# v3: optional status sections (e.g. the streaming driver's
# ``streaming`` block) snapshotted into the manifest at write time.
# v4: optional ``spans`` list ({name, id, parent, start_ns, dur_ns} on
# the time.time_ns() clock); event ``t`` offsets are on that clock too.
MANIFEST_VERSION = 4

# span records kept per run; later spans are counted in the
# ``telemetry.spans_dropped`` counter (their timers still accumulate)
MAX_SPANS = 4096
# jax.monitoring duration events recorded as spans that end when the
# event fires (the work is already done, so no profiler annotation)
_JIT_SPAN_NAMES = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}

_ACTIVE: contextvars.ContextVar["RunTelemetry | None"] = (
    contextvars.ContextVar("peasoup_tpu_telemetry", default=None)
)

# jax.monitoring event-name substrings worth keeping (compile +
# lowering); everything else (tracing cache misses etc.) is noise here.
# "saved" events (e.g. compilation-cache compile_time_saved) are
# SAVINGS estimates, not durations — they can legitimately be negative
# on a slow cache hit and don't belong in a compile-time table.
_JIT_EVENT_KEYS = ("compile", "lower")
_JIT_EVENT_SKIP = ("saved",)
# count events worth keeping as counters: persistent compilation-cache
# traffic. A cache HIT still emits a backend_compile duration event
# (the executable deserialises inside the compile path), so "programs
# really compiled" is backend_compile count minus cache_hits — the
# split campaign done-records and bench.py report.
_JIT_COUNT_EVENT_MARK = "/jax/compilation_cache/"
_jit_listener_installed = False


def current() -> "RunTelemetry":
    """The active run's telemetry, or the module-level no-op sink."""
    return _ACTIVE.get() or NOOP


def _install_jit_listener() -> None:
    """One process-wide jax.monitoring listener forwarding to whatever
    telemetry is active at event time (the registry has no unregister,
    so per-run listeners would accumulate)."""
    global _jit_listener_installed
    if _jit_listener_installed:
        return
    _jit_listener_installed = True
    try:
        from jax import monitoring

        def _on_duration(event: str, duration: float, **kw) -> None:
            tel = _ACTIVE.get()
            if (
                tel is not None
                and duration >= 0  # durations only, not savings deltas
                and any(k in event for k in _JIT_EVENT_KEYS)
                and not any(k in event for k in _JIT_EVENT_SKIP)
            ):
                tel.record_jit(event, float(duration))
                if event in _JIT_SPAN_NAMES:
                    tel.record_past_span(
                        _JIT_SPAN_NAMES[event], float(duration)
                    )

        monitoring.register_event_duration_secs_listener(_on_duration)

        def _on_event(event: str, **kw) -> None:
            tel = _ACTIVE.get()
            if tel is not None and _JIT_COUNT_EVENT_MARK in event:
                tel.incr(event.strip("/").replace("/", "."))

        monitoring.register_event_listener(_on_event)
    except Exception:
        pass  # no monitoring API: manifests simply lack jit stats


def persistent_cache_counters(tel: "RunTelemetry") -> tuple[int, int]:
    """(hits, misses) of the persistent XLA compilation cache recorded
    by this telemetry's run — both 0 when the cache is disabled."""
    return (
        int(tel.counters.get("jax.compilation_cache.cache_hits", 0)),
        int(tel.counters.get("jax.compilation_cache.cache_misses", 0)),
    )


_annotation_cls = None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` (a no-op context without JAX):
    inactive unless a profiler trace is running."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation as _annotation_cls
        except Exception:
            _annotation_cls = lambda name: contextlib.nullcontext()  # noqa: E731
    return _annotation_cls(name)


class Span:
    """One span of a run, opened by :meth:`RunTelemetry.begin` (or a
    ``with RunTelemetry.stage(...)`` block) and closed by :meth:`end`."""

    __slots__ = ("name", "id", "parent", "start_ns", "end_ns", "_tel",
                 "_annotation")

    def __init__(self, tel: "RunTelemetry", name: str, start_ns: int):
        self._tel = tel
        self.name = name
        self.start_ns = start_ns
        self.end_ns: int | None = None
        self.id: int | None = None
        self.parent: int | None = None
        self._annotation = None

    @property
    def seconds(self) -> float:
        """Duration so far (until now while the span is open)."""
        end = self.end_ns if self.end_ns is not None else time.time_ns()
        return (end - self.start_ns) / 1e9

    def end(self) -> float:
        """Close the span and return its seconds. Spans still open
        inside it on this thread (left by an exception) close with it;
        closing twice is harmless."""
        self._tel._end(self)
        return self.seconds


class RunTelemetry:
    """Counters, gauges, stage timers, spans and an event log for one
    run."""

    def __init__(
        self,
        run_id: str | None = None,
        capture_device_trace: bool = False,
        enabled: bool = True,
    ) -> None:
        self.enabled = enabled
        self.run_id = run_id or (
            time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
            + f"-{os.getpid()}"
        )
        self.capture_device_trace = capture_device_trace
        # one clock for spans and event offsets: time.time_ns(), which
        # JAX profiler traces use too (up to one offset per trace)
        self._t0_ns = time.time_ns()
        self.created_unix = self._t0_ns / 1e9
        self._t0 = time.perf_counter()  # uptime base of the live layer
        self.context: dict = {}
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.timers: dict[str, float] = {}
        self.events: list[dict] = []
        self.jit: dict[str, list] = {}  # event -> [count, total_s]
        self.device_trace: dict | None = None
        # live state read by the heartbeat/flight-recorder layer
        self.current_stage: str | None = None
        self._stage_stack: list[str] = []
        self._lock = threading.Lock()  # stage stack and span timers
        self.spans: list[dict] = []
        self._span_ids = itertools.count()
        self._thread = threading.local()  # .spans: this thread's open spans
        self._span_timed: set[str] = set()  # timer keys a span wrote
        self.progress_state: dict = {}
        self._listeners: list = []
        # named live-status providers (name -> zero-arg callable or
        # plain dict); snapshotted by the status.json heartbeat AND
        # into the manifest — how a long-lived driver (the streaming
        # loop) exposes a structured section without the heartbeat
        # knowing its schema
        self.status_sections: dict = {}
        if enabled:
            _install_jit_listener()
            # every run carries the process's resilience accounting
            # (retries, degradations, injected faults, thread crashes)
            # as a status section in status.json and the manifest.
            # stats.py is dependency-free, so no import cycle.
            from ..resilience.stats import STATS

            self.status_sections["resilience"] = STATS.snapshot

    # --- recording ----------------------------------------------------
    def set_context(self, **fields) -> None:
        """Free-form run context (command, input file, config knobs)."""
        if self.enabled:
            self.context.update(fields)

    def incr(self, name: str, by: float = 1) -> None:
        if self.enabled:
            self.counters[name] = self.counters.get(name, 0) + by

    def fetched(self, host_array):
        """Count a blocking device-to-host transfer that produced
        ``host_array`` in ``device.fetches`` and ``device.fetch_bytes``;
        returns the array."""
        if self.enabled:
            self.incr("device.fetches")
            self.incr("device.fetch_bytes", int(host_array.nbytes))
        return host_array

    def gauge(self, name: str, value: float) -> None:
        """Last-write-wins point-in-time value."""
        if self.enabled:
            self.gauges[name] = value

    def gauge_max(self, name: str, value: float) -> None:
        """High-water-mark gauge."""
        if self.enabled:
            self.gauges[name] = max(self.gauges.get(name, value), value)

    def event(self, kind: str, **fields) -> dict | None:
        """Append a structured record to the adaptive-event log. Field
        values must be JSON-serialisable (stringify exceptions)."""
        return self._event_at(time.time_ns(), kind, fields)

    def _event_at(self, t_ns: int, kind: str, fields: dict) -> dict | None:
        if not self.enabled:
            return None
        rec = {
            "t": round((t_ns - self._t0_ns) / 1e9, 6),
            "kind": kind,
            **fields,
        }
        self.events.append(rec)
        for fn in self._listeners:
            try:
                fn(rec)
            except Exception:
                pass  # a broken listener must never fail the run
        return rec

    def set_status_section(self, name: str, provider) -> None:
        """Register a named status section: ``provider`` is a zero-arg
        callable returning a JSON-serialisable dict (or a plain dict).
        Heartbeat snapshots and the manifest embed it top-level under
        ``name`` (pick names the schema knows, e.g. ``streaming``)."""
        if self.enabled:
            self.status_sections[name] = provider

    def snapshot_sections(self) -> dict:
        """Evaluate every registered status section (a failing provider
        yields an ``error`` stub rather than failing the snapshot)."""
        out = {}
        for name, provider in self.status_sections.items():
            try:
                out[name] = provider() if callable(provider) else provider
            except Exception as exc:
                out[name] = {"error": f"{type(exc).__name__}: {exc!s:.200}"}
        return out

    def add_listener(self, fn) -> None:
        """Subscribe ``fn(record)`` to every event as it is recorded
        (the flight recorder's ring-buffer feed)."""
        if fn not in self._listeners:
            self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        if fn in self._listeners:
            self._listeners.remove(fn)

    def set_stage(self, name: str, at_ns: int | None = None) -> None:
        """Mark the run's current pipeline stage (drivers that time
        stages manually call this at each phase boundary; drivers using
        :meth:`stage` get it for free). Recorded as a ``stage`` event so
        the flight recorder and manifest keep the transition history."""
        if not self.enabled or name == self.current_stage:
            return
        self.current_stage = name
        self._event_at(
            time.time_ns() if at_ns is None else at_ns, "stage",
            {"name": name},
        )

    def set_progress(
        self, done: float, total: float | None = None, unit: str = ""
    ) -> None:
        """Update the run's live progress counter (read by the
        status.json heartbeat for rate/ETA and by the stall watchdog)."""
        if not self.enabled:
            return
        self.progress_state = {
            "done": float(done),
            "total": float(total) if total is not None else None,
            "unit": unit,
            "t": round((time.time_ns() - self._t0_ns) / 1e9, 6),
            "updated_unix": time.time(),
        }

    @contextlib.contextmanager
    def stage(self, name: str):
        """A span around the with-block, yielding its :class:`Span`
        (see :meth:`begin`). An exception leaving the block closes the
        span but keeps the current stage where it failed, for the
        flight recorder's ``stage_at_abort``."""
        sp = self.begin(name)
        try:
            yield sp
        except BaseException:
            self._end(sp, failed=True)
            raise
        self._end(sp)

    def begin(self, name: str) -> Span:
        """Open a span; close it with :meth:`Span.end`, which returns its
        seconds. A span is an accumulating stage timer (same key space
        as the overview.xml ``<execution_times>`` table), the run's
        *current* stage until it closes (the live status.json heartbeat
        and ``stage`` events), a ``peasoup.<name>`` profiler annotation,
        and a record in the manifest's ``spans`` list whose ``parent``
        is the innermost span open on the same thread. The disabled
        sink only measures the span's seconds."""
        sp = Span(self, name, time.time_ns())
        if not self.enabled:
            return sp
        sp._annotation = _annotation("peasoup." + name)
        sp._annotation.__enter__()
        stack = self._open_spans()
        sp.parent = stack[-1].id if stack else None
        sp.id = next(self._span_ids)
        stack.append(sp)
        with self._lock:
            self._stage_stack.append(name)
        self.set_stage(name, at_ns=sp.start_ns)
        return sp

    def _open_spans(self) -> list:
        stack = getattr(self._thread, "spans", None)
        if stack is None:
            stack = self._thread.spans = []
        return stack

    def _end(self, sp: Span, failed: bool = False) -> None:
        if sp.end_ns is not None:
            return
        end_ns = time.time_ns()
        if not self.enabled:
            sp.end_ns = end_ns
            return
        stack = self._open_spans()
        if sp in stack:
            while stack[-1] is not sp:  # unclosed inner spans close here
                self._close(stack.pop(), end_ns, failed=True)
            stack.pop()
        self._close(sp, end_ns, failed)

    def _close(self, sp: Span, end_ns: int, failed: bool) -> None:
        sp.end_ns = end_ns
        sp._annotation.__exit__(None, None, None)
        self._record_span(sp.name, sp.id, sp.parent, sp.start_ns,
                          end_ns - sp.start_ns)
        with self._lock:
            self.timers[sp.name] = self.timers.get(sp.name, 0.0) + (
                (end_ns - sp.start_ns) / 1e9
            )
            self._span_timed.add(sp.name)
            # the innermost entry of this name: spans of other threads
            # may have opened above it
            for i in range(len(self._stage_stack) - 1, -1, -1):
                if self._stage_stack[i] == sp.name:
                    del self._stage_stack[i]
                    break
            back = self._stage_stack[-1] if self._stage_stack else None
        if back is not None and not failed:
            self.set_stage(back, at_ns=end_ns)

    def _record_span(self, name, sid, parent, start_ns, dur_ns) -> None:
        # ids are taken in opening order, so a kept span's parent is
        # always kept too
        if sid < MAX_SPANS:
            self.spans.append({"name": name, "id": sid, "parent": parent,
                               "start_ns": start_ns,
                               "dur_ns": max(0, dur_ns)})
        else:
            self.incr("telemetry.spans_dropped")

    def record_past_span(self, name: str, seconds: float) -> None:
        """Record a span that ends now and lasted ``seconds`` (work
        reported after it finished, such as a jit compile), under the
        innermost span open on this thread. It takes no timer, stage or
        profiler annotation."""
        if not self.enabled:
            return
        end_ns = time.time_ns()
        stack = self._open_spans()
        dur_ns = max(0, int(seconds * 1e9))
        self._record_span(name, next(self._span_ids),
                          stack[-1].id if stack else None,
                          end_ns - dur_ns, dur_ns)

    def add_timer(self, name: str, seconds: float) -> None:
        """Merge an externally measured duration into a stage timer."""
        if self.enabled:
            self.timers[name] = self.timers.get(name, 0.0) + seconds

    def merge_timers(self, timers: dict[str, float]) -> None:
        """Merge a pipeline's timer table (its overview.xml timings). A
        key that a span of this run already timed is skipped: the
        table's value is that span's, and merging it would count it
        twice."""
        for k, v in timers.items():
            if k not in self._span_timed:
                self.add_timer(k, float(v))

    def record_jit(self, event: str, seconds: float) -> None:
        if self.enabled:
            st = self.jit.setdefault(event, [0, 0.0])
            st[0] += 1
            st[1] += seconds

    def capture_device_memory(self, tag: str | None = None) -> None:
        """The local devices' memory high-water mark where the backend
        reports it (``memory_stats`` is absent on some backends, e.g.
        CPU) as ``memory.peak_bytes``, and as ``memory.<tag>.peak_bytes``
        when a ``tag`` is given."""
        if not self.enabled:
            return
        try:
            import jax

            devs = jax.local_devices()
        except Exception:
            return
        peak = 0
        for d in devs:
            try:
                stats = d.memory_stats() or {}
            except Exception:
                stats = {}
            peak = max(
                peak,
                int(
                    stats.get("peak_bytes_in_use")
                    or stats.get("bytes_in_use")
                    or 0
                ),
            )
        if peak:
            if tag is not None:
                self.gauge_max(f"memory.{tag}.peak_bytes", peak)
            self.gauge_max("memory.peak_bytes", peak)

    # --- activation ---------------------------------------------------
    @contextlib.contextmanager
    def activate(self):
        """Make this object the run's ambient telemetry (``current()``)
        for the duration of the with-block."""
        token = _ACTIVE.set(self if self.enabled else None)
        try:
            yield self
        finally:
            _ACTIVE.reset(token)

    @contextlib.contextmanager
    def device_capture(self):
        """Opt-in profiler capture: wrap the block in a
        ``jax.profiler.trace`` and fold the parsed per-scope
        device-time/bytes attribution (tools/scope_trace.py) into the
        manifest. No-op unless ``capture_device_trace`` was requested —
        tracing costs memory and wall time."""
        if not (self.enabled and self.capture_device_trace):
            yield
            return
        from ..tools.scope_trace import scope_trace

        with scope_trace() as res:
            yield
        self.device_trace = {
            "device_s": res.device_s,
            "phases": res.phase_seconds(),
            "table": [
                {"scope": k, "seconds": s, "gigabytes": gb}
                for k, s, gb in res.table()
            ],
        }

    # --- serialisation ------------------------------------------------
    def _platform(self) -> dict:
        info: dict = {"python": sys.version.split()[0]}
        try:
            import jax

            info["jax"] = jax.__version__
            info["backend"] = jax.default_backend()
            info["process_index"] = jax.process_index()
            info["process_count"] = jax.process_count()
            info["devices"] = [
                {
                    "id": d.id,
                    "platform": str(d.platform),
                    "kind": str(d.device_kind),
                }
                for d in jax.local_devices()
            ]
        except Exception:
            pass  # platform info must never fail a run
        return info

    def to_manifest(
        self, aborted: bool = False, abort_reason: str | None = None
    ) -> dict:
        """The versioned run manifest. Key order is fixed (schema and
        version lead) so manifests diff cleanly in text tools too.
        ``aborted=True`` marks a partial manifest dumped by the flight
        recorder for a run that did not complete."""
        plat = self._platform()
        man = {
            "schema": MANIFEST_SCHEMA,
            "version": MANIFEST_VERSION,
            "run_id": self.run_id,
            "created_unix": self.created_unix,
            "duration_s": round(
                max(0, time.time_ns() - self._t0_ns) / 1e9, 6
            ),
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            # per-host shard tags, duplicated from platform so the
            # --merge reader need not reach into nested dicts
            "process_index": int(plat.get("process_index", 0)),
            "process_count": int(plat.get("process_count", 1)),
            "platform": plat,
            "context": self.context,
            "timers": {k: self.timers[k] for k in sorted(self.timers)},
            "counters": {
                k: self.counters[k] for k in sorted(self.counters)
            },
            "gauges": {k: self.gauges[k] for k in sorted(self.gauges)},
            "jit": {
                k: {"count": v[0], "seconds": v[1]}
                for k, v in sorted(self.jit.items())
            },
            "events": self.events,
            "device_trace": self.device_trace,
        }
        if self.spans:
            man["spans"] = self.spans
        for name, val in self.snapshot_sections().items():
            if name not in man:  # sections can never shadow core keys
                man[name] = val
        if aborted:
            man["aborted"] = True
            man["abort_reason"] = abort_reason
            man["stage_at_abort"] = self.current_stage
            man["progress_at_abort"] = (
                dict(self.progress_state) if self.progress_state else None
            )
        return man

    def write(
        self,
        path: str,
        aborted: bool = False,
        abort_reason: str | None = None,
    ) -> dict:
        """Serialise the manifest to ``path`` (atomic replace) and
        return it."""
        man = self.to_manifest(aborted=aborted, abort_reason=abort_reason)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(man, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
        return man


NOOP = RunTelemetry(enabled=False)


def load_manifest(path: str) -> dict:
    """Load + validate a telemetry.json manifest."""
    with open(path) as f:
        man = json.load(f)
    if man.get("schema") != MANIFEST_SCHEMA:
        raise ValueError(
            f"{path}: not a {MANIFEST_SCHEMA} manifest "
            f"(schema={man.get('schema')!r})"
        )
    if int(man.get("version", 0)) > MANIFEST_VERSION:
        raise ValueError(
            f"{path}: manifest version {man.get('version')} is newer "
            f"than this reader (supports <= {MANIFEST_VERSION})"
        )
    return man
