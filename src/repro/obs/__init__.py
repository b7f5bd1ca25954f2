"""Structured compile-pipeline tracing + metrics (DESIGN.md §15).

A hierarchical span tracer threaded through the whole pipeline::

    from repro import obs

    with obs.span("time.probe", ii=4):
        ...                       # timed; nests under the enclosing span
    obs.event("cache.memory.hit", ii=4)   # zero-duration instant
    obs.incr("space.restarts")            # named counter on the tracer

Design contract (the "overhead contract"):

* **Disabled is the default and costs almost nothing.** The module-level
  ``_ACTIVE`` tracer is ``None`` unless a CLI or test installs one;
  ``span()`` / ``event()`` / ``incr()`` check it first (and, for spans and
  events, whether a JAX profiler trace is collecting) and return a shared
  ``_NULL_SPAN`` singleton without allocating. Instrumentation sites can
  therefore stay inline in hot loops (mapper rounds, solver probes).
* **Stdlib only, imports nothing from ``repro``.** Like
  ``repro.api.options``, this module must be importable from every layer
  (core, service workers, CLIs) without cycles.
* **One timeline across processes.** Timestamps are wall-epoch anchored
  (``time.time()`` at tracer start + ``perf_counter`` deltas), so span
  shards written by service worker processes merge onto the parent's
  timeline with pid/tid attribution intact.
* **The device's clock too.** While a JAX profiler trace is collecting,
  every span also enters a ``jax.profiler.TraceAnnotation`` with the same
  name and attributes (``set`` forwards to its metadata; an event is a
  zero-length annotation), so it lands in the profiler's host timeline
  beside the device's ops. jax is looked up in ``sys.modules``, never
  imported here: a process that has not imported it has no such trace.

Serialization is the Chrome trace-event JSON flavor (``"X"`` complete
events, ``"i"`` instants, ``"M"`` metadata) that Perfetto / ``chrome://
tracing`` load directly; ``tools/trace_report.py`` summarizes the same
file into a self-time table.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from contextlib import contextmanager

__all__ = [
    "Tracer",
    "append_shard",
    "enabled",
    "env_enabled",
    "event",
    "get_tracer",
    "incr",
    "install_tracer",
    "merge_shards",
    "recording",
    "session",
    "span",
    "tracing",
]

# The process-global active tracer. ``None`` means tracing is disabled and
# every obs call short-circuits through the no-op fast path below.
_ACTIVE: "Tracer | None" = None

# ``jax.profiler.TraceAnnotation`` and its ``is_enabled`` once jax is
# imported (see _annotation).
_ANNOTATION = None
_PROFILING = None


def _annotation():
    """The profiler's annotation class while a JAX profiler trace is
    collecting, else None. The class is cached once jax is imported; until
    then each call is one lookup in ``sys.modules``."""
    global _ANNOTATION, _PROFILING
    if _PROFILING is None:
        cls = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
        if cls is None:
            return None
        _ANNOTATION, _PROFILING = cls, cls.is_enabled
    return _ANNOTATION if _PROFILING() else None


def env_enabled() -> bool:
    """True when the ``REPRO_TRACE`` environment variable is truthy."""
    return os.environ.get("REPRO_TRACE", "").strip().lower() not in (
        "", "0", "false", "no", "off",
    )


def enabled() -> bool:
    """True when a tracer is currently installed."""
    return _ACTIVE is not None


def recording() -> bool:
    """True when a span would be recorded anywhere: a tracer is installed or
    a JAX profiler trace is collecting. Sites guard costly attribute work
    with it."""
    return _ACTIVE is not None or _annotation() is not None


def get_tracer() -> "Tracer | None":
    return _ACTIVE


class _NullSpan:
    """Shared no-op span: the disabled-mode fast path (zero allocation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):  # pragma: no cover - trivial
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """A live span: records an ``"X"`` complete event on the tracer (if one
    is installed) and a profiler annotation (if a trace is collecting)."""

    __slots__ = ("_tracer", "_annotation", "name", "args", "_t0", "_ts")

    def __init__(self, tracer: "Tracer | None", annotation, name: str, args: dict):
        self._tracer = tracer
        self._annotation = annotation(name, **args) if annotation is not None else None
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._ts = 0.0

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._tracer is not None:
            self._t0 = time.perf_counter()
            self._ts = self._tracer._now_us()
        return self

    def __exit__(self, *exc):
        if self._tracer is not None:
            dur_us = (time.perf_counter() - self._t0) * 1e6
            self._tracer._emit_complete(self.name, self._ts, dur_us, self.args)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False

    def set(self, **attrs):
        """Attach/override attributes after the span started."""
        self.args.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        return self


class Tracer:
    """Collects trace events for one process; thread-safe appends.

    Events are stored as Chrome trace-event dicts (``ts``/``dur`` in
    microseconds since the Unix epoch, so shards from different processes
    share one timeline).
    """

    def __init__(self, process_name: str = "repro"):
        self.process_name = process_name
        self.pid = os.getpid()
        # wall-epoch anchor: wall time at construction + perf_counter deltas
        self._epoch_us = time.time() * 1e6
        self._anchor = time.perf_counter()
        self._lock = threading.Lock()
        self.events: list[dict] = []
        self.counters: dict[str, int] = {}

    # -- time ------------------------------------------------------------
    def _now_us(self) -> float:
        return self._epoch_us + (time.perf_counter() - self._anchor) * 1e6

    # -- event emission ---------------------------------------------------
    def _emit_complete(self, name, ts_us, dur_us, args) -> None:
        ev = {
            "name": name,
            "cat": name.split(".", 1)[0],
            "ph": "X",
            "ts": round(ts_us, 1),
            "dur": round(dur_us, 1),
            "pid": self.pid,
            "tid": threading.get_ident() % 1_000_000,
            "args": args,
        }
        with self._lock:
            self.events.append(ev)

    def emit_instant(self, name: str, args: dict) -> None:
        ev = {
            "name": name,
            "cat": name.split(".", 1)[0],
            "ph": "i",
            "ts": round(self._now_us(), 1),
            "pid": self.pid,
            "tid": threading.get_ident() % 1_000_000,
            "s": "t",
            "args": args,
        }
        with self._lock:
            self.events.append(ev)

    def incr(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def adopt(self, events: list) -> None:
        """Merge externally produced events (worker shards) into this trace."""
        with self._lock:
            self.events.extend(events)

    def drain(self) -> list[dict]:
        """Atomically take (and clear) the accumulated events.

        The rotation primitive for unbounded-lifetime sessions (the compile
        daemon, DESIGN.md §16.5): the caller serializes each drained segment
        to its own Chrome-JSON file so the in-memory event list never grows
        for the life of the process. Counters are cumulative and are NOT
        cleared — they describe the session, not the segment.
        """
        with self._lock:
            events, self.events = self.events, []
        return events

    def write_segment(self, path: str, events: list[dict]) -> None:
        """Write one drained segment as a standalone Chrome trace document
        (same schema as :meth:`write`, so ``tools/trace_report.py`` loads
        rotated daemon segments and one-shot CLI traces identically)."""
        with open(path, "w") as f:
            json.dump(self._document(events), f)

    # -- serialization ----------------------------------------------------
    def _document(self, events: list[dict]) -> dict:
        """The Chrome trace document of ``events``: one ``process_name``
        record per pid, then the events, then the counters."""
        pids = sorted({e["pid"] for e in events} | {self.pid})
        meta = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": self.process_name if pid == self.pid
                     else f"worker-{pid}"},
        } for pid in pids]
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        with self._lock:
            if self.counters:
                doc["otherData"] = {"counters": dict(self.counters)}
        return doc

    def to_chrome(self) -> dict:
        """The Perfetto-loadable Chrome trace-event JSON document."""
        with self._lock:
            events = list(self.events)
        return self._document(events)

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)

    # -- rollups ----------------------------------------------------------
    def span_totals(self) -> dict[str, float]:
        """Total duration (seconds) per span name, across all processes."""
        totals: dict[str, float] = {}
        with self._lock:
            for e in self.events:
                if e.get("ph") == "X":
                    totals[e["name"]] = totals.get(e["name"], 0.0) + e["dur"] / 1e6
        return totals


# -- module-level API (the only names instrumentation sites use) ----------

def span(name: str, **attrs):
    """Context manager timing a named span; no-op when nothing records."""
    t = _ACTIVE
    # _annotation() inlined: this is the path every disabled span takes
    profiling = _PROFILING
    if profiling is None:
        cls = _annotation()
    else:
        cls = _ANNOTATION if profiling() else None
    if cls is None and t is None:
        return _NULL_SPAN
    return _Span(t, cls, name, attrs)


def event(name: str, **attrs) -> None:
    """Record a zero-duration instant event; no-op when nothing records."""
    t = _ACTIVE
    if t is not None:
        t.emit_instant(name, attrs)
    annotation = _annotation()
    if annotation is not None:
        with annotation(name, **attrs):
            pass


def incr(name: str, n: int = 1) -> None:
    """Bump a named counter on the active tracer; no-op when disabled."""
    t = _ACTIVE
    if t is not None:
        t.incr(name, n)


def install_tracer(tracer: "Tracer | None") -> "Tracer | None":
    """Install ``tracer`` as the process-global tracer; return the previous.

    The non-scoped variant of :func:`tracing` for callers whose lifetime is
    not a ``with`` block — the compile daemon installs its session tracer at
    start and restores the previous one at shutdown (DESIGN.md §16.5).
    """
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    return prev


@contextmanager
def tracing(tracer: "Tracer | None" = None):
    """Install ``tracer`` (or a fresh one) as the process-global tracer."""
    global _ACTIVE
    t = tracer if tracer is not None else Tracer()
    prev = _ACTIVE
    _ACTIVE = t
    try:
        yield t
    finally:
        _ACTIVE = prev


@contextmanager
def session(path: "str | None" = None, *, enable: bool = False,
            process_name: str = "repro"):
    """CLI entry point: trace when asked, write Chrome JSON on exit.

    Installs a tracer when ``path`` is given, ``enable`` is true, or
    ``REPRO_TRACE`` is set — otherwise yields ``None`` and the whole
    pipeline stays on the no-op fast path. When a tracer is already
    active (nested session), it is reused and ownership stays outside.
    """
    global _ACTIVE
    if not (path or enable or env_enabled()):
        yield None
        return
    if _ACTIVE is not None:
        yield _ACTIVE
        return
    t = Tracer(process_name=process_name)
    _ACTIVE = t
    try:
        yield t
    finally:
        _ACTIVE = None
        if path:
            t.write(path)


# -- cross-process shards -------------------------------------------------

def append_shard(trace_dir: str, events: list, counters: "dict | None" = None) -> None:
    """Append this process's events to its per-pid JSONL shard file.

    Workers call this after each job; the parent merges with
    :func:`merge_shards`. One file per pid means no cross-process locking.
    """
    if not events and not counters:
        return
    path = os.path.join(trace_dir, f"shard-{os.getpid()}.jsonl")
    lines = [json.dumps(e) for e in events]
    if counters:
        lines.append(json.dumps({"_counters": counters}))
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")


def merge_shards(trace_dir: str) -> "tuple[list[dict], dict[str, int]]":
    """Read every per-pid shard in ``trace_dir``; return (events, counters)."""
    events: list[dict] = []
    counters: dict[str, int] = {}
    try:
        names = sorted(os.listdir(trace_dir))
    except OSError:
        return events, counters
    for fn in names:
        if not (fn.startswith("shard-") and fn.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(trace_dir, fn)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    rec = json.loads(line)
                    if "_counters" in rec:
                        for k, v in rec["_counters"].items():
                            counters[k] = counters.get(k, 0) + v
                    else:
                        events.append(rec)
        except (OSError, ValueError):
            continue  # a torn shard must not sink the batch
    return events, counters
