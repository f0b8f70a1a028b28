"""Deterministic fault injection + retry/backoff + quarantine report.

The fault-tolerance layer has three moving parts, all here:

* **Injection harness** — named fault sites instrumented into the read
  and dispatch paths fire *deterministically* (site + occurrence
  counting, no randomness) under an :func:`inject_faults` scope.  Used
  by ``tests/test_faults.py`` to prove each fault class takes its
  designed path; zero-cost in production (one module-global ``is
  None`` check per site).
* **Retry with bounded exponential backoff** —
  :func:`retry_transient` for transient I/O,
  :func:`backoff_delays` shared with the device-dispatch retry in
  ``kernels/device.py``.
* **Quarantine report** — :class:`QuarantineReport` accumulates the
  exact coordinates (file / row group / column / page) and error class
  of every unit a ``ShardedScan(on_error="quarantine")`` isolated.

Fault sites (``site`` argument to :meth:`FaultInjector.inject`):

====================================  =====================================
site                                  instrumented where / supported kinds
====================================  =====================================
``io.reader.chunk_read``              ``FileReader.iter_selected_chunks``
                                      — ``oserror``, ``transient``,
                                      ``corrupt``, ``truncate``
``io.chunk.page_payload``             CPU page loop (``io/chunk.py``)
                                      — ``corrupt``, ``truncate``
``io.pages.page_decode``              ``decode_data_page_v1/v2``
                                      — ``corrupt``, ``truncate``
``io.pages.page_write``               native page assembly
                                      (``_write_page_native``; firing
                                      drops the page to the pure
                                      writer, bytes identical)
                                      — ``transient``
``kernels.device.page_payload``       device plan page loop
                                      — ``corrupt``, ``truncate``
``kernels.device.page_dispatch``      device plan, per data page
                                      — ``dispatch``
``kernels.device.unit_dispatch``      ``_finish_unit`` (per unit)
                                      — ``dispatch``
``format.footer.tail``                8-byte length+magic tail read
                                      (``format/footer.py``)
                                      — ``corrupt``, ``truncate``
``format.footer.blob``                footer thrift blob read
                                      — ``corrupt``, ``truncate``
``io.reader.open``                    ``FileReader.__init__`` (per open)
                                      — ``oserror``, ``transient``
``io.chunk.hang``                     chunk byte read (the hedgeable
                                      primary/mirror read callable in
                                      ``io/reader.py``; ctx carries
                                      ``file`` so a rule can hang ONE
                                      replica) — ``hang``
``kernels.device.hang``               device dispatch
                                      (``_finish_unit``) — ``hang``
``format.pageindex``                  page-index / bloom-filter blob
                                      reads (``io/reader.py``) —
                                      ``oserror``, ``transient``,
                                      ``corrupt``, ``truncate``
``io.remote.open``                    byte-range source open
                                      (``io/source.py``) — ``oserror``,
                                      ``transient``
``io.remote.throttle``                per range request, before the
                                      read (the HTTP-429 slot;
                                      ``io/source.py``) — ``transient``
``io.remote.range``                   range request payload
                                      (``io/source.py``; short/truncated
                                      responses are detected and raised
                                      as transient, never returned) —
                                      ``oserror``, ``transient``,
                                      ``corrupt``, ``truncate``
``dataset.manifest.write``            dataset manifest / commit-journal
                                      publication (``dataset/
                                      manifest.py``, before the tmp
                                      write) — ``oserror``,
                                      ``transient``
``dataset.manifest.load``             manifest / journal blob read
                                      (``dataset/manifest.py``) —
                                      ``oserror``, ``transient``,
                                      ``corrupt``, ``truncate``
``dataset.file.promote``              staged data-file rename into its
                                      partition directory
                                      (``dataset/writer.py``) —
                                      ``oserror``, ``transient``
====================================  =====================================

Kinds: ``oserror`` raises ``OSError(EIO)``; ``transient`` raises
:class:`~tpuparquet.errors.TransientIOError`; ``dispatch`` raises
:class:`~tpuparquet.errors.DeviceDispatchError`; ``corrupt`` XORs one
byte of the stream (``offset=``, ``xor=``); ``truncate`` drops the
tail (``keep=``); ``hang`` BLOCKS the calling thread (``seconds=``,
default 30) — but releases early the moment its :func:`inject_faults`
scope exits, so abandoned hedge/deadline worker threads never outlive
a test.  Each rule fires on the first ``times`` matching calls after
skipping ``after`` — "fail twice then succeed" is ``times=2``, which a
retry loop must survive.

The active injector is a **process-global** (not thread-local): the
pipelined reader plans on worker threads and faults must reach them.
Each firing increments ``DecodeStats.faults_injected`` on the firing
thread's collector and appends to :attr:`FaultInjector.log`.
"""

from __future__ import annotations

import contextlib
import errno as _errno
import os
import sys
import threading
import time
import zlib

from .errors import DeviceDispatchError, TransientIOError

__all__ = [
    "FaultInjector",
    "inject_faults",
    "fault_point",
    "filter_bytes",
    "retry_transient",
    "backoff_delays",
    "is_transient",
    "QuarantineReport",
    "SITES",
    "ChaosSchedule",
    "chaos_scope",
]

#: The fault-site registry: every instrumented site name and the
#: fault kinds it supports.  Sites match rules by STRING EQUALITY, so
#: a drifted name doesn't error — it just never fires; this registry
#: is the single source of truth that the instrumentation hooks, the
#: docstring table above, and the matrices in ``tests/test_faults.py``
#: are all checked against (``tools/analyze`` fault-site pass).  Add
#: the row HERE first when instrumenting a new site.
SITES: dict[str, tuple] = {
    "io.reader.open": ("oserror", "transient"),
    "io.reader.chunk_read": ("oserror", "transient",
                             "corrupt", "truncate"),
    "io.chunk.page_payload": ("corrupt", "truncate"),
    "io.chunk.hang": ("hang",),
    "io.pages.page_decode": ("corrupt", "truncate"),
    "io.pages.page_write": ("transient",),
    "kernels.device.page_payload": ("corrupt", "truncate"),
    "kernels.device.page_dispatch": ("dispatch",),
    "kernels.device.unit_dispatch": ("dispatch",),
    "kernels.device.hang": ("hang",),
    "format.footer.tail": ("corrupt", "truncate"),
    "format.footer.blob": ("corrupt", "truncate"),
    "format.pageindex": ("oserror", "transient",
                         "corrupt", "truncate"),
    "io.remote.open": ("oserror", "transient"),
    "io.remote.throttle": ("transient",),
    "io.remote.range": ("oserror", "transient",
                        "corrupt", "truncate"),
    "dataset.manifest.write": ("oserror", "transient"),
    "dataset.manifest.load": ("oserror", "transient",
                              "corrupt", "truncate"),
    "dataset.file.promote": ("oserror", "transient"),
}

_active: "FaultInjector | None" = None


class _Rule:
    __slots__ = ("site", "kind", "times", "after", "kw", "match",
                 "seen", "fired")

    def __init__(self, site, kind, times, after, match, kw):
        self.site = site
        self.kind = kind
        self.times = times
        self.after = after
        self.match = match or {}
        self.kw = kw
        self.seen = 0    # matching calls observed
        self.fired = 0   # faults actually delivered


class FaultInjector:
    """Deterministic fault plan: rules added with :meth:`inject`, a
    :attr:`log` of ``(site, kind, ctx)`` for every fault delivered."""

    def __init__(self):
        self.rules: list[_Rule] = []
        self.log: list[dict] = []
        self._lock = threading.Lock()

    def inject(self, site: str, kind: str, *, times: int = 1,
               after: int = 0, match: dict | None = None, **kw) -> _Rule:
        """Arm a rule: at ``site``, deliver ``kind`` on the first
        ``times`` matching calls after skipping ``after``.  ``match``
        restricts by context equality (e.g. ``match={"column": "a"}``).
        Extra ``kw`` parameterize the kind (``offset``/``xor`` for
        ``corrupt``, ``keep`` for ``truncate``)."""
        r = _Rule(site, kind, times, after, match, kw)
        with self._lock:
            self.rules.append(r)
        return r

    # -- firing (called from the instrumented sites) ---------------------

    def _next_rule(self, site: str, ctx: dict,
                   kinds: tuple) -> "_Rule | None":
        with self._lock:
            for r in self.rules:
                if r.site != site or r.kind not in kinds:
                    continue
                if any(ctx.get(k) != v for k, v in r.match.items()):
                    continue
                r.seen += 1
                if r.seen <= r.after or r.fired >= r.times:
                    continue
                r.fired += 1
                self.log.append(
                    {"site": site, "kind": r.kind, **ctx})
                return r
        return None

    def _record_stats(self, site: str, kind: str, ctx: dict) -> None:
        from .obs.recorder import flight
        from .stats import current_stats

        # flight recorder sees every delivered fault, collector or not
        flight(f"fault:{kind}", site=site, **ctx)
        st = current_stats()
        if st is not None:
            st.faults_injected += 1
            if st.events is not None:
                st.events.fault(site=site, kind=kind, **ctx)

    def fire_raise(self, site: str, ctx: dict) -> None:
        # byte-kinds (corrupt/truncate) never match here: a site name
        # can host BOTH hooks (fault_point for failures, filter_bytes
        # for the data it read), and a byte rule must wait for the
        # byte hook rather than be consumed by this one
        r = self._next_rule(site, ctx, ("oserror", "transient",
                                        "dispatch", "hang"))
        if r is None:
            return
        self._record_stats(site, r.kind, ctx)
        if r.kind == "hang":
            # simulate a read/dispatch that never returns: block until
            # the cap, or until this injector's scope exits (so
            # abandoned hedge/deadline workers release with the test)
            seconds = r.kw.get("seconds", 30.0)
            t0 = time.monotonic()
            while _active is self and \
                    time.monotonic() - t0 < seconds:
                time.sleep(0.005)
            return
        if r.kind == "oserror":
            raise OSError(_errno.EIO,
                          f"injected I/O error at {site}")
        if r.kind == "transient":
            raise TransientIOError(
                f"injected transient fault at {site}", **_coords(ctx))
        raise DeviceDispatchError(
            f"injected device dispatch failure at {site}",
            **_coords(ctx))

    def fire_bytes(self, site: str, data, ctx: dict):
        r = self._next_rule(site, ctx, ("corrupt", "truncate"))
        if r is None:
            return data
        self._record_stats(site, r.kind, ctx)
        if r.kind == "truncate":
            keep = r.kw.get("keep", len(data) // 2)
            return bytes(data[:keep])
        buf = bytearray(data)
        if not buf:
            return data
        off = r.kw.get("offset", len(buf) // 2) % len(buf)
        buf[off] ^= r.kw.get("xor", 0xFF) or 0xFF
        return bytes(buf)


def _coords(ctx: dict) -> dict:
    return {k: ctx[k] for k in ("file", "row_group", "column", "page")
            if k in ctx}


@contextlib.contextmanager
def inject_faults():
    """Scope with a fresh active :class:`FaultInjector` (yields it).
    Process-global and not reentrant — one scope at a time; intended
    for tests and chaos drills."""
    global _active
    if _active is not None:
        raise RuntimeError("inject_faults scopes do not nest")
    inj = FaultInjector()
    _active = inj
    try:
        yield inj
    finally:
        _active = None


def fault_point(site: str, **ctx) -> None:
    """Instrumentation hook: may raise an injected fault.  No-op (one
    global ``is None`` check) when no injector is active."""
    ch = _chaos
    if ch is not None:
        ch.perturb(site)
    inj = _active
    if inj is not None:
        inj.fire_raise(site, ctx)


def filter_bytes(site: str, data, **ctx):
    """Instrumentation hook for byte streams: returns ``data`` (the
    common case, zero-copy) or an injected corruption/truncation of
    it; may also raise for read-failure kinds."""
    ch = _chaos
    if ch is not None:
        ch.perturb(site)
    inj = _active
    if inj is not None:
        return inj.fire_bytes(site, data, ctx)
    return data


# ----------------------------------------------------------------------
# Schedule chaos: deterministic interleaving perturbation
# ----------------------------------------------------------------------
#
# The fault sites double as NAMED YIELD POINTS: under a
# :func:`chaos_scope`, every ``fault_point``/``filter_bytes`` call may
# sleep a few microseconds or force a GIL release, and the interpreter
# switch interval is pinned to a seed-derived aggressive value.  The
# perturbation PLAN is a pure function of (seed, site, occurrence
# ordinal) — no global ``random`` state, no wall-clock input — so a
# seed names one chaos schedule.  What chaos runs assert is OUTPUT
# invariance (byte-identical scan/write results, exact counter
# conservation) across seeds, not schedule identity: the OS may still
# interleave threads differently, and that is the point.

_chaos: "ChaosSchedule | None" = None


class ChaosSchedule:
    """A seeded interleaving-perturbation plan over the fault-site
    registry (zero-cost when inactive: one module-global ``is None``
    check per site, same discipline as the injector)."""

    #: per-site occurrence draw: (do nothing, yield GIL, short sleep)
    _SLEEP_MAX_S = 200e-6

    def __init__(self, seed: int):
        self.seed = int(seed)
        # benign-race counters: a lost increment only shifts which
        # perturbation a thread draws, never the data path — keeping
        # this lock-free means chaos adds no lock-order edges
        self._counts: dict[str, int] = {}
        self.perturbations = 0
        import random

        rng = random.Random(self.seed)
        #: seed-derived interpreter switch interval, aggressive enough
        #: to force switches inside critical regions (default is 5ms)
        self.switch_interval = 10 ** rng.uniform(-6.0, -4.0)

    def _draw(self, site: str, n: int) -> float:
        key = f"{self.seed}:{site}:{n}".encode()
        return (zlib.crc32(key) & 0xFFFFFFFF) / 0x100000000

    def perturb(self, site: str) -> None:
        n = self._counts.get(site, 0)
        self._counts[site] = n + 1
        u = self._draw(site, n)
        if u < 0.4:
            return
        self.perturbations += 1
        if u < 0.7:
            time.sleep(0)          # force a GIL release / reschedule
        else:
            # a short sleep moves this thread to the back of the line
            time.sleep((u - 0.7) / 0.3 * self._SLEEP_MAX_S)


@contextlib.contextmanager
def chaos_scope(seed: int | None = None):
    """Scope with an active :class:`ChaosSchedule` (yields it):
    perturbs thread interleavings at every registered fault site and
    pins a seed-derived ``sys.setswitchinterval``.  ``seed`` falls
    back to ``TPQ_CHAOS_SEED`` (default 0).  Process-global and not
    reentrant, like :func:`inject_faults` (the two compose: chaos
    perturbs first, then the injector fires)."""
    global _chaos
    if _chaos is not None:
        raise RuntimeError("chaos_scope scopes do not nest")
    if seed is None:
        seed = _env_int("TPQ_CHAOS_SEED", 0)
    sched = ChaosSchedule(seed)
    prev = sys.getswitchinterval()
    sys.setswitchinterval(sched.switch_interval)
    _chaos = sched
    try:
        yield sched
    finally:
        _chaos = None
        sys.setswitchinterval(prev)


# ----------------------------------------------------------------------
# Retry with bounded exponential backoff
# ----------------------------------------------------------------------

_TRANSIENT_ERRNOS = frozenset(
    getattr(_errno, name)
    for name in ("EIO", "EAGAIN", "EBUSY", "EINTR", "ETIMEDOUT",
                 "ENETRESET", "ECONNRESET", "ESTALE")
    if hasattr(_errno, name)
)

_PERMANENT_OS = (FileNotFoundError, PermissionError, IsADirectoryError,
                 NotADirectoryError)


def is_transient(exc: BaseException) -> bool:
    """Is this failure worth retrying?  TransientIOError always;
    plain OSError only for retryable errnos — a FileNotFoundError
    will not heal with backoff."""
    if isinstance(exc, TransientIOError):
        return True
    if isinstance(exc, _PERMANENT_OS):
        return False
    if isinstance(exc, (TimeoutError, InterruptedError, ConnectionError)):
        return True
    if isinstance(exc, OSError):
        return exc.errno in _TRANSIENT_ERRNOS
    return False


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def backoff_delays(retries: int | None = None,
                   base: float | None = None,
                   cap: float | None = None,
                   jitter: float | None = None,
                   seed: int | None = None) -> list[float]:
    """The bounded exponential schedule: ``[base*2^0, base*2^1, ...]``
    clamped to ``cap``, one entry per retry.  Knobs (env):
    ``TPQ_IO_RETRIES`` (default 3), ``TPQ_RETRY_BASE_S`` (0.01),
    ``TPQ_RETRY_MAX_S`` (0.5).

    ``jitter`` spreads each delay multiplicatively by up to ±that
    fraction (decorrelates retry storms across a fleet; env
    ``TPQ_RETRY_JITTER``, default 0.0 = the exact schedule).  The
    jitter stream is drawn from a LOCAL PRNG, never global ``random``
    state, seeded by ``seed`` (else ``TPQ_RETRY_SEED``, else a
    per-process derivation from the pid — distinct hosts/processes
    get distinct schedules, which is what breaks the herd).  With
    ``seed``/``TPQ_RETRY_SEED`` pinned the schedule is fully
    deterministic, so retry-timing assertions are reproducible rather
    than flaky."""
    if retries is None:
        retries = _env_int("TPQ_IO_RETRIES", 3)
    if base is None:
        base = _env_float("TPQ_RETRY_BASE_S", 0.01)
    if cap is None:
        cap = _env_float("TPQ_RETRY_MAX_S", 0.5)
    if jitter is None:
        jitter = _env_float("TPQ_RETRY_JITTER", 0.0)
    delays = [min(base * (2 ** i), cap) for i in range(max(retries, 0))]
    if jitter:
        import random

        if seed is None:
            # per-process default: decorrelate across the fleet while
            # staying stable within one process; pin TPQ_RETRY_SEED
            # (or pass seed=) for cross-run determinism
            seed = _env_int("TPQ_RETRY_SEED", os.getpid() ^ 0x7E9)
        rng = random.Random(seed)
        delays = [max(d * (1.0 + jitter * (2.0 * rng.random() - 1.0)),
                      0.0)
                  for d in delays]
    return delays


def retry_transient(fn, *, retries: int | None = None,
                    base: float | None = None, cap: float | None = None,
                    sleep=time.sleep, counter: str = "io_retries"):
    """Call ``fn()``; on a transient failure (:func:`is_transient`)
    retry up to ``retries`` times with bounded exponential backoff.
    Permanent errors and the final exhausted attempt propagate
    unchanged.  Each retry increments ``DecodeStats.<counter>`` on the
    active collector.

    A transient error carrying a ``retry_after_s`` attribute (an
    HTTP 429/503 with a ``Retry-After`` header, mapped by
    :class:`~tpuparquet.io.source.HttpByteRangeSource`) stretches
    that retry's sleep to the origin's hint — bounded by the backoff
    cap, so a hostile header can never stall a scan — and never
    shortens it below the scheduled delay."""
    from .stats import current_stats

    if cap is None:
        cap = _env_float("TPQ_RETRY_MAX_S", 0.5)
    delays = backoff_delays(retries, base, cap)
    for delay in delays:
        try:
            return fn()
        except Exception as e:
            if not is_transient(e):
                raise
            hint = getattr(e, "retry_after_s", None)
            if hint is not None:
                delay = max(delay, min(float(hint), cap))
            st = current_stats()
            if st is not None:
                setattr(st, counter, getattr(st, counter) + 1)
            sleep(delay)
    return fn()


# ----------------------------------------------------------------------
# Quarantine report
# ----------------------------------------------------------------------

class QuarantineReport:
    """Where the bad units went: one entry per quarantined scan unit,
    carrying exact coordinates and the error class.  JSON-serializable
    (:meth:`as_dicts` / :meth:`from_dicts`) so it rides scan cursors
    and the cross-host all-gather."""

    def __init__(self, entries: list[dict] | None = None):
        self.entries: list[dict] = list(entries or [])

    def add(self, *, unit: int, file, row_group: int,
            error: BaseException) -> dict:
        entry = {
            "unit": unit,
            "file": file,
            "row_group": row_group,
            "error": type(error).__name__,
            "message": str(error),
        }
        return self._finish(entry, error)

    def add_file(self, *, file, error: BaseException, **extra) -> dict:
        """A FILE-granularity entry: the whole file was rejected at
        open/validate time (torn footer, strict-metadata reject), or a
        salvaged file's unreadable remainder.  ``unit``/``row_group``
        are None — no unit ever existed for the lost data."""
        entry = {
            "unit": None,
            "file": file,
            "row_group": None,
            "error": type(error).__name__,
            "message": str(error),
        }
        entry.update(extra)
        return self._finish(entry, error)

    def _finish(self, entry: dict, error: BaseException) -> dict:
        # ScanErrors pinpoint deeper: column / page / a more precise
        # file label from an inner layer
        coords = getattr(error, "coordinates", None)
        if callable(coords):
            for k, v in coords().items():
                if k == "file":
                    entry["file_detail"] = v
                elif k != "row_group":
                    entry[k] = v
        self.entries.append(entry)
        return entry

    def units(self) -> list[int]:
        """Unit ordinals of unit-level entries (file-level entries have
        no unit and are listed by :meth:`files`)."""
        return [e["unit"] for e in self.entries if e["unit"] is not None]

    def files(self) -> list:
        """Files with a file-granularity entry (open/validate reject
        or salvaged remainder)."""
        return [e["file"] for e in self.entries if e["unit"] is None]

    def __len__(self) -> int:
        return len(self.entries)

    def __bool__(self) -> bool:
        return bool(self.entries)

    def as_dicts(self) -> list[dict]:
        return [dict(e) for e in self.entries]

    @classmethod
    def from_dicts(cls, entries) -> "QuarantineReport":
        return cls([dict(e) for e in entries or []])

    def merge_from(self, other: "QuarantineReport") -> None:
        self.entries.extend(dict(e) for e in other.entries)

    # identity of an entry for resume dedup: the coordinates + error
    # class (NOT the message/extras — a re-opened bad file may phrase
    # its failure slightly differently run to run)
    _KEY_FIELDS = ("unit", "file", "row_group", "column", "page",
                   "error")

    @classmethod
    def entry_key(cls, e: dict) -> tuple:
        return tuple(e.get(k) for k in cls._KEY_FIELDS)

    def merge_unique(self, entries) -> int:
        """Append entries whose coordinate key isn't already present;
        returns how many were added.  Used on cursor resume: a resumed
        scan re-opens its sources, so a file already quarantined in
        the checkpointed cursor is rejected AGAIN at open time — the
        fresh entry must not duplicate the checkpointed one."""
        seen = {self.entry_key(e) for e in self.entries}
        added = 0
        for e in entries or []:
            k = self.entry_key(e)
            if k in seen:
                continue
            seen.add(k)
            self.entries.append(dict(e))
            added += 1
        return added

    def summary(self) -> str:
        if not self.entries:
            return "quarantine: empty"
        lines = [f"quarantine: {len(self.entries)} entr(y/ies)"]
        for e in self.entries:
            at = ", ".join(
                f"{k}={e[k]}" for k in
                ("file", "row_group", "column", "page")
                if e.get(k) is not None)
            head = f"unit {e['unit']}" if e.get("unit") is not None \
                else "file"
            lines.append(f"  {head} [{at}]: "
                         f"{e['error']}: {e['message']}")
        return "\n".join(lines)
