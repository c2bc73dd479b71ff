"""Phase spans: structured tracing layered on the wall-clock timer registry.

:class:`span` is a drop-in superset of :class:`sheeprl_tpu.utils.timer.timer`:
it accumulates wall seconds into the same global registry (so the
``Time/sps_*`` rate gauges keep working unchanged), and — when a run tracer is
active — additionally

- emits one Chrome trace-event per scope into a per-run JSONL file
  (``<log_dir>/telemetry/trace.jsonl``), and
- mirrors the scope into :class:`jax.profiler.TraceAnnotation`, so the same
  phase names show up inside XLA/TensorBoard device profiles captured with
  ``metric.profiler``.

The tracer is installed by :func:`sheeprl_tpu.obs.telemetry.setup_telemetry`;
with no tracer installed a ``span`` is exactly a ``timer`` (no file handles,
no jax calls, no device syncs), so instrumented code paths cost nothing in
un-instrumented runs.

Trace-event schema (one JSON object per line; the "complete event" subset of
the Chrome trace-event format):

``{"name": str, "cat": phase, "ph": "X", "ts": µs, "dur": µs,
  "pid": jax process index, "tid": host thread id,
  "args": {"parent": enclosing open span on this thread or null,
           "burst": the run counter ``train_bursts`` when the span opened}}``

(``parent`` says which span caused this one, ``burst`` which train cycle it
belongs to; the per-thread stack of open spans behind ``parent`` exists only
while a tracer is installed. Work that runs on another thread on a caller's
behalf — a host callback of a dispatched program — names the caller's span
itself: ``span(..., parent=current_span())`` taken on the caller's thread)

plus ``{"ph": "M", ...}`` thread-name metadata and ``{"ph": "C", ...}``
counter samples from the device poller. Load in Perfetto / chrome://tracing
after wrapping the lines in a JSON array (``jq -s . trace.jsonl``).
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import ContextDecorator, contextmanager
from typing import Any, Dict, Optional

from sheeprl_tpu.obs import counters as _counters
from sheeprl_tpu.obs import hist as _hist
from sheeprl_tpu.utils.timer import timer

__all__ = ["span", "TraceWriter", "current_span", "get_tracer", "set_tracer", "scoped_compile_key"]

#: events buffered before a file flush (bounds write syscalls in hot loops)
_FLUSH_EVERY = 128

_TRACER: Optional["TraceWriter"] = None

#: per-thread stack of open span names, grown only under an installed tracer
_OPEN = threading.local()


def get_tracer() -> Optional["TraceWriter"]:
    """The run's active tracer, or None (telemetry disabled)."""
    return _TRACER


def set_tracer(tracer: Optional["TraceWriter"]) -> None:
    global _TRACER
    _TRACER = tracer


def current_span() -> Optional[str]:
    """The innermost span open on this thread while a tracer is installed;
    None with no tracer (and then no stack is looked at)."""
    if _TRACER is None:
        return None
    stack = getattr(_OPEN, "stack", None)
    return stack[-1] if stack else None


@contextmanager
def scoped_compile_key():
    """Around a dispatch that may compile a program whose ``jax.named_scope``
    names a device profile is to show.

    jax keys its persistent compile cache on the program with its debug
    information stripped, so a program that differs from a cached one in its
    scopes alone gets the cached executable back — without the scopes. While
    the tracer mirrors spans into the profiler (``xla_annotations``), the
    key includes the metadata for the duration of this scope: the annotated
    program gets a cache entry of its own, and un-instrumented runs stay on
    the entries they have. Two config writes per use with a tracer; with
    none, nothing (no jax call).
    """
    tracer = _TRACER
    if tracer is None or not tracer.xla_annotations:
        yield
        return
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


class TraceWriter:
    """Thread-safe buffered Chrome trace-event JSONL writer.

    ``path=None`` runs the writer file-less: events are still produced (and
    fed to ``ring`` — the flight recorder's bounded buffer) but nothing
    touches the disk. That is how a run with ``metric.telemetry.trace=false``
    keeps its flight recorder armed.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        xla_annotations: bool = True,
        ring=None,
        pid: Optional[int] = None,
        process_name: Optional[str] = None,
        origin: Optional[float] = None,
    ):
        self.path = path
        self.xla_annotations = bool(xla_annotations)
        self.ring = ring
        if path is not None:
            os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
            self._file = open(path, "w")
        else:
            self._file = None
        self._lock = threading.Lock()
        self._buffer: list[str] = []
        # writers sharing one process can share one origin so their ts
        # values compare directly (the serve tracer's two lanes do)
        self._origin = float(origin) if origin is not None else time.perf_counter()
        self._named_threads: set[int] = set()
        if pid is not None:
            # explicit track id: plane players and env workers must not
            # collide with the learner's pid 0 in a merged Perfetto view
            # (and must not import jax just to pick a number)
            self._pid = int(pid)
        else:
            try:
                import jax

                self._pid = int(jax.process_index())
            except Exception:
                self._pid = 0
        # wall-clock anchor so tools/trace_view.py can align per-rank files
        # captured by processes with different perf_counter origins
        self._emit(
            {
                "ph": "M",
                "name": "clock_sync",
                "pid": self._pid,
                "args": {"unix_ts": time.time()},
            }
        )
        if process_name:
            # Perfetto/chrome://tracing label the whole track with this
            self._emit(
                {
                    "ph": "M",
                    "name": "process_name",
                    "pid": self._pid,
                    "args": {"name": process_name},
                }
            )

    # -- time ---------------------------------------------------------------

    def now(self) -> float:
        """Monotonic seconds; pass to :meth:`complete` as the span start."""
        return time.perf_counter()

    def _us(self, t: float) -> float:
        return (t - self._origin) * 1e6

    # -- events -------------------------------------------------------------

    def _emit(self, event: Dict[str, Any]) -> None:
        if self.ring is not None:
            self.ring.record(event)
        if self._file is None:
            return
        line = json.dumps(event)
        with self._lock:
            self._buffer.append(line)
            if len(self._buffer) >= _FLUSH_EVERY:
                self._flush_locked()

    def _thread_meta(self, tid: int) -> None:
        if tid in self._named_threads:
            return
        self._named_threads.add(tid)
        self._emit(
            {
                "ph": "M",
                "name": "thread_name",
                "pid": self._pid,
                "tid": tid,
                "args": {"name": threading.current_thread().name},
            }
        )

    def complete(
        self,
        name: str,
        cat: Optional[str],
        t0: float,
        t1: Optional[float] = None,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        """One completed span ``[t0, t1]`` (``ph: X``). ``args`` attaches
        correlation payload (e.g. a serve trace id) to the event."""
        t1 = time.perf_counter() if t1 is None else t1
        tid = threading.get_ident()
        self._thread_meta(tid)
        self._emit(
            {
                "name": name,
                "cat": cat or "run",
                "ph": "X",
                "ts": round(self._us(t0), 1),
                "dur": round((t1 - t0) * 1e6, 1),
                "pid": self._pid,
                "tid": tid,
                **({"args": args} if args else {}),
            }
        )

    def counter(self, name: str, values: Dict[str, float]) -> None:
        """A sampled counter series (``ph: C``) — e.g. per-device HBM use."""
        self._emit(
            {
                "name": name,
                "ph": "C",
                "ts": round(self._us(time.perf_counter()), 1),
                "pid": self._pid,
                "args": values,
            }
        )

    def instant(self, name: str, cat: Optional[str] = None, args: Optional[Dict[str, Any]] = None) -> None:
        """A zero-duration marker (``ph: i``) — e.g. a health-guard firing."""
        self._emit(
            {
                "name": name,
                "cat": cat or "health",
                "ph": "i",
                "s": "g",
                "ts": round(self._us(time.perf_counter()), 1),
                "pid": self._pid,
                "tid": threading.get_ident(),
                **({"args": args} if args else {}),
            }
        )

    def annotation(self, name: str):
        """A ``jax.profiler.TraceAnnotation`` for the span, or None."""
        if not self.xla_annotations:
            return None
        try:
            import jax

            return jax.profiler.TraceAnnotation(name)
        except Exception:
            return None

    # -- lifecycle ----------------------------------------------------------

    def _flush_locked(self) -> None:
        if self._buffer and self._file is not None and not self._file.closed:
            self._file.write("\n".join(self._buffer) + "\n")
            self._file.flush()
        self._buffer.clear()

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def close(self) -> None:
        with self._lock:
            self._flush_locked()
            if self._file is not None and not self._file.closed:
                self._file.close()


class span(ContextDecorator):
    """``with span("Time/train_time", phase="train"): ...``

    Accumulates into the global :class:`timer` registry under ``name`` (same
    semantics, including the concurrent-reset re-register path) and, when a
    tracer is active, emits a trace event categorized under ``phase`` and
    mirrors the scope into the XLA profiler. ``parent`` names the span that
    caused this one where that span is open on another thread; it is read
    only while a tracer is installed.
    """

    def __init__(self, name: str, metric: Any = None, phase: Optional[str] = None, parent: Optional[str] = None):
        self.name = name
        self.phase = phase
        self._parent = parent
        self._timer = timer(name, metric)
        self._t0: Optional[float] = None
        self._annotation = None
        self._args: Optional[Dict[str, Any]] = None

    def __enter__(self):
        tracer = _TRACER
        if tracer is not None or _hist.installed() is not None:
            self._t0 = time.perf_counter()
        if tracer is not None:
            stack = getattr(_OPEN, "stack", None)
            if stack is None:
                stack = _OPEN.stack = []
            self._args = {
                "parent": self._parent or (stack[-1] if stack else None),
                "burst": _counters.train_bursts(),
            }
            stack.append(self.name)
            self._annotation = tracer.annotation(self.name)
            if self._annotation is not None:
                self._annotation.__enter__()
        self._timer.__enter__()
        return self

    def __exit__(self, *exc):
        self._timer.__exit__(*exc)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        args, self._args = self._args, None
        if args is not None:
            _OPEN.stack.pop()  # spans are scopes: last opened, first closed
        if self._t0 is not None:
            t0, self._t0 = self._t0, None
            t1 = time.perf_counter()
            # histograms first: a slow-span trigger fired here lands its
            # flight dump before this very event rotates into the ring
            _hist.observe(self.name, t1 - t0)
            tracer = _TRACER
            if tracer is not None:
                tracer.complete(self.name, self.phase, t0, t1, args)
        return False
