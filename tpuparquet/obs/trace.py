"""Causal scan tracing: lightweight spans with parent/child structure.

The flight recorder (``obs/recorder.py``) answers *what just
happened*; the metrics registry (``obs/live.py``) answers *how much,
in total*.  Neither can answer the question a slow scan actually
poses: **which stage of which unit's read → plan → stage → dispatch →
gather chain bounds the wall**, across the column-parallel plan pool,
hedged replica reads, deadline workers and multiple hosts.  That is a
causality question, and this module is the Dapper-style answer: every
pipeline stage records a **span** — ``(trace_id, span_id, parent_id,
name, start, dur, status, coordinates, payload)`` — and the parent
relationship is propagated ambiently via :mod:`contextvars` (captured
at submit time and re-entered by pool/hedge/deadline workers), so the
spans of one scan form one connected tree no matter how many threads
executed them.  ``parquet-tool doctor`` walks that tree
(:mod:`~tpuparquet.obs.attribution`) and names the bounding stage.

Cost model — exactly the flight-recorder discipline:

* **off (default)**: one module-global load + ``is None`` check per
  hot site; hot call sites guard the call itself
  (``if _trace._active is not None: _trace.emit_span(...)``) so even
  the kwargs build is skipped — enforced structurally by the
  ``tools/analyze`` recorder-guard pass.
* **on** (``TPQ_TRACE=1``; an integer > 1 sets the per-thread ring
  depth): one bounded ``deque.append`` of a small dict per span.
  Spans are stage/chunk granularity — never per value.  Rings live in
  a :class:`~tpuparquet.obs.recorder.ThreadSlots` (per-thread
  registration, dead-owner retirement), so memory stays bounded under
  the deadline/hedge layers' disposable-worker churn.

Sampling (``TPQ_TRACE_SAMPLE``, default 1.0) decides per TRACE, not
per span: an unsampled scan records nothing at all (its root context
never arms), so every recorded trace is complete — a partial tree
would defeat the critical-path walk.  Spans emitted with no ambient
trace context are dropped for the same reason: no orphans, ever.

Timebase: ``time.perf_counter()`` throughout (monotonic,
high-resolution); the tracer keeps one ``(wall, perf)`` anchor pair so
exports (:func:`~tpuparquet.obs.export.spans_otlp`) can map span
starts back to epoch time.

Stage boundaries of the unit pipeline (plan, plan wait, transfer,
dispatch, drain) go through one call, :class:`stage`: a
``jax.profiler.TraceAnnotation`` named ``tpq.<stage>`` (always — it
lands on the device trace's clock whenever a profiler trace runs, and
costs well under a microsecond when none does), the stage's
``DecodeStats`` field, the span below when tracing is on, the event
log's phase span when the collector keeps one, and the sampling
profiler's stage mark when it is armed.

Export: ``TPQ_TRACE_EXPORT`` names a file the scan drivers write at
scan end (atomic tmp + replace) — ``*.perfetto.json`` /
``*.chrome.json`` → Chrome trace-event JSON (load at
ui.perfetto.dev), ``*.otlp.json`` → OTLP-shaped ``resourceSpans``
JSON, anything else → the native ``tpq-trace`` envelope
``parquet-tool doctor`` reads.  Cross-host,
``shard.distributed.allgather_traces`` folds every host's spans
(annotated with their process index) into one fleet-wide list.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import os
import threading
import time
from collections import deque

from ..stats import current_stats
from . import profiler as _profiler
from .recorder import ThreadSlots

__all__ = [
    "Tracer", "tracer", "set_tracing", "trace_default",
    "sample_default", "trace_export_default", "current_ctx", "adopt",
    "start_trace", "end_trace", "open_span", "close_span",
    "emit_span", "stage", "trace_scope", "snapshot_spans",
    "clear_spans",
]

#: Ambient (trace_id, span_id) of the innermost open span — the
#: parent every new span attaches to.  Per-thread by construction
#: (each thread has its own context); workers that run a caller's
#: work on another thread re-enter the caller's value via
#: :func:`adopt`.
_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "tpq_trace_ctx", default=None)

_DEFAULT_RING = 8192


def trace_default() -> int:
    """Ring depth from ``TPQ_TRACE``: ``0``/unset/invalid = tracing
    off, ``1`` = on at the default depth, > 1 = on at that per-thread
    ring depth."""
    try:
        v = int(os.environ.get("TPQ_TRACE", "0"))
    except ValueError:
        return 0
    if v <= 0:
        return 0
    return _DEFAULT_RING if v == 1 else v


def sample_default() -> float:
    """Trace sampling rate from ``TPQ_TRACE_SAMPLE`` (fraction of
    traces recorded, default 1.0; clamped to [0, 1])."""
    try:
        v = float(os.environ.get("TPQ_TRACE_SAMPLE", ""))
    except ValueError:
        return 1.0
    return min(max(v, 0.0), 1.0)


def trace_export_default() -> str | None:
    """Scan-end trace export path (``TPQ_TRACE_EXPORT``; None=off)."""
    return os.environ.get("TPQ_TRACE_EXPORT") or None


class Tracer:
    """Per-thread bounded rings of completed spans + the id wells.

    Span ids are process-unique monotone ints (``itertools.count`` —
    its ``__next__`` is atomic under the GIL, no lock on the span
    path); trace ids embed the pid so multi-host merges can't
    collide.  Deterministic sampling: trace N of rate r records iff
    ``int(N*r) > int((N-1)*r)`` — reproducible without a PRNG."""

    def __init__(self, ring: int = _DEFAULT_RING,
                 sample: float = 1.0):
        self.ring = ring
        self.sample = sample
        self.anchor_wall = time.time()
        self.anchor_perf = time.perf_counter()
        self._span_ids = itertools.count(1)
        self._trace_ids = itertools.count(1)
        self._slots = ThreadSlots(
            make=lambda: deque(maxlen=ring),
            fold=lambda retired, dead: retired.extend(dead))

    def _sampled(self, n: int) -> bool:
        r = self.sample
        if r >= 1.0:
            return True
        if r <= 0.0:
            return False
        return int(n * r) > int((n - 1) * r)

    def record(self, rec: dict) -> None:
        self._slots.get().append(rec)

    def snapshot(self, trace: str | None = None) -> list[dict]:
        """All completed spans (every thread's ring + the retired
        fold), start-sorted; ``trace`` filters to one trace id."""
        out: list[dict] = []
        for r in self._slots.all():
            out.extend(list(r))
        if trace is not None:
            out = [s for s in out if s.get("trace") == trace]
        out.sort(key=lambda s: s["t0"])
        return out

    def clear(self) -> None:
        for r in self._slots.all():
            r.clear()

    def anchor(self) -> dict:
        """The wall/perf pair exports use to map span starts to epoch
        seconds: ``epoch = wall + (t0 - perf)``."""
        return {"wall": self.anchor_wall, "perf": self.anchor_perf}


#: The active tracer, or None when tracing is off — the single gate
#: every hot-path hook checks (one global load + ``is None``, the
#: recorder._active discipline).  Initialized from the environment at
#: import; reconfigure at runtime with :func:`set_tracing`.
_active: Tracer | None = None


def _init_from_env() -> None:
    global _active
    n = trace_default()
    _active = Tracer(n, sample_default()) if n > 0 else None


_init_from_env()


def tracer() -> Tracer | None:
    """The active tracer (None when tracing is off)."""
    return _active


def set_tracing(enabled: bool = True, *, ring: int | None = None,
                sample: float | None = None) -> Tracer | None:
    """Reconfigure at runtime: ``True`` installs a FRESH tracer
    (dropping recorded spans), ``False`` disables.  Returns the new
    tracer (tests and A/B benches flip this without re-importing)."""
    global _active
    if not enabled:
        _active = None
        return None
    _active = Tracer(ring if ring is not None
                     else (trace_default() or _DEFAULT_RING),
                     sample if sample is not None else sample_default())
    return _active


# ----------------------------------------------------------------------
# Context propagation
# ----------------------------------------------------------------------

def current_ctx():
    """The ambient ``(trace_id, span_id)`` pair (None outside any
    sampled trace).  Capture this at submit time and hand it to a
    worker thread, which re-enters it with :func:`adopt` — the
    cross-thread half of causal propagation."""
    if _active is None:
        return None
    return _ctx.get()


@contextlib.contextmanager
def adopt(ctx):
    """Run a block under a captured trace context (no-op for None):
    the worker-side half of cross-thread propagation — every span the
    block emits parents under the capturing site's open span."""
    if ctx is None:
        yield
        return
    token = _ctx.set(ctx)
    # the profiler mirrors every context transition (contextvars are
    # unreadable cross-thread, so the sampler needs its own map)
    if _profiler._active is not None:
        _profiler.ctx_push(ctx[0], ctx[1], None)
    try:
        yield
    finally:
        _reset(token)
        if _profiler._active is not None:
            _profiler.ctx_pop(ctx[0], ctx[1])


def _reset(token) -> None:
    # a generator resumed from a different context activation cannot
    # reset the token it minted — fall back to clearing the var
    try:
        _ctx.reset(token)
    except ValueError:
        _ctx.set(None)


# ----------------------------------------------------------------------
# Span lifecycle
# ----------------------------------------------------------------------

def start_trace(label: str, **fields):
    """Begin a new trace (the scan drivers call this once per run):
    allocates a trace id, applies the sampling decision, opens the
    root span and pushes it as the ambient context.  Returns an
    opaque handle for :func:`end_trace`, or None when tracing is off
    or this trace was not sampled — in which case every child
    ``emit_span``/``open_span`` is dropped too (whole-trace
    sampling)."""
    tr = _active
    if tr is None:
        return None
    n = next(tr._trace_ids)
    if not tr._sampled(n):
        return None
    tid = f"{os.getpid():x}-{n}"
    sid = next(tr._span_ids)
    token = _ctx.set((tid, sid))
    if _profiler._active is not None:
        _profiler.ctx_push(tid, sid, "scan", label=label)
    return {"trace": tid, "span": sid, "parent": None, "name": "scan",
            "t0": time.perf_counter(), "token": token,
            "fields": {"label": label, **fields}}


def end_trace(handle, status: str = "ok", **fields) -> None:
    """Close a :func:`start_trace` root: emits the root span and pops
    the ambient context.  No-op for None handles."""
    if handle is None:
        return
    close_span(handle, status=status, **fields)


def open_span(name: str, *, push: bool = True, parent=None, **fields):
    """Open a span that children will attach to.

    Parent resolution: explicit ``parent`` ctx, else the ambient
    context.  Returns None — and records nothing — when tracing is
    off or there is no enclosing sampled trace (no orphan spans).
    ``push=True`` makes this span the ambient context until
    :func:`close_span` (same-thread nesting); ``push=False`` leaves
    the ambient context alone and the caller hands ``ctx_of(handle)``
    to workers explicitly (the pipelined reader's unit spans, whose
    open/close straddle generator yields)."""
    tr = _active
    if tr is None:
        return None
    ctx = parent if parent is not None else _ctx.get()
    if ctx is None:
        return None
    sid = next(tr._span_ids)
    token = _ctx.set((ctx[0], sid)) if push else None
    if _profiler._active is not None:
        if push:
            _profiler.ctx_push(ctx[0], sid, name)
        else:
            _profiler.span_note(ctx[0], sid, name)
    return {"trace": ctx[0], "span": sid, "parent": ctx[1],
            "name": name, "t0": time.perf_counter(), "token": token,
            "fields": fields}


def ctx_of(handle):
    """The ``(trace_id, span_id)`` of an open span handle (None for
    None) — what a submitting site captures for its workers."""
    if handle is None:
        return None
    return (handle["trace"], handle["span"])


def close_span(handle, status: str = "ok", **fields) -> None:
    """Emit an open span with its measured duration; pops the ambient
    context when the span pushed one.  No-op for None handles (the
    disabled path), and safe when tracing was disabled mid-span.

    The context pop is conditional on the ambient context still being
    THIS span's: an abandoned scan generator finalized later (GC) must
    not clobber the context of whatever trace the thread has since
    started — a non-LIFO token reset would restore the pre-span value
    over the newer trace's root and silently drop all its spans."""
    if handle is None:
        return
    if handle["token"] is not None:
        cur = _ctx.get()
        if cur is not None and cur[0] == handle["trace"] \
                and cur[1] == handle["span"]:
            _reset(handle["token"])
            if _profiler._active is not None:
                _profiler.ctx_pop(handle["trace"], handle["span"])
    tr = _active
    if tr is None:
        return
    t1 = time.perf_counter()
    rec = {"trace": handle["trace"], "span": handle["span"],
           "parent": handle["parent"], "name": handle["name"],
           "t0": handle["t0"], "dur": t1 - handle["t0"],
           "tid": threading.get_ident(), "status": status}
    if handle["fields"]:
        rec.update(handle["fields"])
    if fields:
        rec.update(fields)
    tr.record(rec)


def emit_span(name: str, t0: float, dur: float, *, status: str = "ok",
              parent=None, **fields) -> None:
    """Record one COMPLETED span (the hot-site form: the call site
    measured ``t0``/``dur`` itself, usually for a counter it was
    already feeding).  Parents to the ambient context (or an explicit
    ``parent`` ctx); dropped when tracing is off or no sampled trace
    encloses the call.

    Hot per-chunk/per-stage sites guard the CALL itself with
    ``_trace._active is not None`` so the disabled path skips even
    the kwargs construction — the recorder-guard analyze pass holds
    ``emit_span`` call sites to the same rule as ``flight``."""
    tr = _active
    if tr is None:
        return
    ctx = parent if parent is not None else _ctx.get()
    if ctx is None:
        return
    rec = {"trace": ctx[0], "span": next(tr._span_ids),
           "parent": ctx[1], "name": name, "t0": t0, "dur": dur,
           "tid": threading.get_ident(), "status": status}
    if fields:
        rec.update(fields)
    tr.record(rec)


@functools.cache
def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use (the
    tracer itself imports no JAX)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class stage:
    """One stage of the unit pipeline, recorded once where it happens::

        with stage("dispatch", "dispatch_s", column=path):
            out[path] = finish(staged)

    On entry: a ``TraceAnnotation("tpq.<name>", **fields)``; the
    span ``name`` when tracing is on (pushed as the ambient context,
    so spans opened inside nest under it; ``parent`` overrides the
    ambient parent); the profiler's stage mark ``name`` when it is
    armed.  On exit: the wall in seconds added to the current
    collector's ``field``, and the thread CPU seconds
    (``time.thread_time``) to its ``cpu`` field; an event-log phase
    span when the collector keeps a log; the span closed, with status
    ``"error"`` when the block raised.  :meth:`note` adds fields known
    only at the end (span and event log; the annotation took its
    fields on entry).  Never hold one across a ``yield``."""

    __slots__ = ("name", "field", "cpu", "parent", "fields", "_ann",
                 "_span", "_ptok", "_t0", "_c0")

    def __init__(self, name: str, field: str | None = None, *,
                 cpu: str | None = None, parent=None, **fields):
        self.name = name
        self.field = field
        self.cpu = cpu
        self.parent = parent
        self.fields = fields

    def note(self, **fields) -> None:
        self.fields.update(fields)

    def __enter__(self):
        self._ann = _annotation()("tpq." + self.name, **self.fields)
        self._ann.__enter__()
        self._span = (open_span(self.name, parent=self.parent,
                                **self.fields)
                      if _active is not None else None)
        self._ptok = (_profiler.stage_begin(self.name)
                      if _profiler._active is not None else None)
        # the CPU reading nests inside the wall reading, so cpu <= wall
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time() if self.cpu is not None else 0.0
        return self

    def __exit__(self, et, ev, tb):
        c1 = time.thread_time() if self.cpu is not None else 0.0
        t1 = time.perf_counter()
        try:
            _profiler.stage_end(self._ptok)
            if self._span is not None:
                self._span["fields"] = self.fields
                close_span(self._span,
                           status="ok" if et is None else "error")
            st = current_stats()
            if st is not None:
                if self.field is not None:
                    setattr(st, self.field,
                            getattr(st, self.field) + (t1 - self._t0))
                if self.cpu is not None:
                    setattr(st, self.cpu,
                            getattr(st, self.cpu) + (c1 - self._c0))
                if st.events is not None:
                    st.events.span(self.name, "decode", self._t0, t1,
                                   tid=threading.get_ident(),
                                   **self.fields)
        finally:
            self._ann.__exit__(et, ev, tb)
        return False


@contextlib.contextmanager
def trace_scope(label: str = "work", **fields):
    """Trace an arbitrary block as its own root trace (the
    tools/tests entry point: ``parquet-tool profile`` wraps its decode
    in one so the doctor can walk it).  Yields the root handle (None
    when tracing is off/unsampled)."""
    h = start_trace(label, **fields)
    try:
        yield h
    except BaseException:
        end_trace(h, status="error")
        raise
    end_trace(h)


def snapshot_spans(trace: str | None = None) -> list[dict]:
    """Completed spans of the active tracer ([] when off)."""
    tr = _active
    return [] if tr is None else tr.snapshot(trace)


def clear_spans() -> None:
    tr = _active
    if tr is not None:
        tr.clear()
