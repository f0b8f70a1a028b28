"""Structured error taxonomy for the scan/decode path.

The reference (and the seed) raised bare ``ValueError`` everywhere on
the decode path, which gives a scan driver no way to tell *corruption*
(permanent — quarantine the unit) from a *transient* I/O hiccup
(retry) from a *device* failure (degrade to the CPU path).  This
module is the taxonomy that makes those policies implementable:

* :class:`CorruptPageError` / :class:`CorruptChunkError` — the bytes
  are wrong (CRC mismatch, truncation, malformed header, impossible
  counts).  Permanent for this file; a fault-tolerant scan quarantines
  the unit and continues.  Both subclass ``ValueError`` so every
  existing ``except ValueError`` caller (and the crash-corpus "clean
  failure" contract in ``tests/test_corpus.py``) keeps working.
* :class:`CorruptFooterError` — the file-level analogue: torn or
  truncated footer, metadata that fails bounds validation.  A sharded
  scan quarantines the whole *file* (or salvages its readable prefix,
  ``format/recover.py``) and continues.
* :class:`TransientIOError` — the read *might* succeed if repeated
  (flaky NFS, throttled object store).  Subclasses ``OSError``;
  :func:`tpuparquet.faults.retry_transient` retries these with bounded
  exponential backoff.
* :class:`DeviceDispatchError` — staging or kernel dispatch to the
  accelerator failed.  The data is fine; the resilient read path
  retries and then degrades to the bit-exact CPU decode
  (``kernels.device.read_row_group_device_resilient``).
* :class:`DeadlineExceededError` / :class:`DispatchDeadlineError` —
  the *time* domain (``tpuparquet/deadline.py``): a watched operation
  ran past its budget.  A hung chunk read becomes
  :class:`DeadlineExceededError` (a :class:`TransientIOError`, so the
  retry/hedge ladder handles it); a hung device dispatch becomes
  :class:`DispatchDeadlineError` (a :class:`DeviceDispatchError`, so
  the dispatch-retry → CPU-fallback ladder handles it).  Both carry
  ``elapsed`` and ``budget`` seconds next to the scan coordinates, so
  a quarantine entry says exactly how long the unit hung.

Every class carries scan coordinates (file / row group / column /
page).  Inner layers raise with what they know; outer layers
:meth:`~ScanError.annotate` the rest as the error propagates, so by
the time a quarantine report sees it the failing unit is pinpointed
exactly.
"""

from __future__ import annotations

__all__ = [
    "ScanError",
    "CorruptPageError",
    "CorruptChunkError",
    "CorruptFooterError",
    "CorruptManifestError",
    "TransientIOError",
    "DeviceDispatchError",
    "DeadlineExceededError",
    "DispatchDeadlineError",
    "ServeStateError",
    "AdmissionRejected",
    "QUARANTINE_ERRORS",
]

_COORD_FIELDS = ("file", "row_group", "column", "page")


class ScanError(Exception):
    """Base of the taxonomy: an error with scan coordinates.

    ``file`` is a path or file index (whatever the raising layer
    knows), ``row_group``/``page`` are ordinals, ``column`` is the
    dotted ``path_in_schema``.  All optional — :meth:`annotate` fills
    blanks as the error crosses layers without clobbering what an
    inner layer already pinned.
    """

    def __init__(self, message: str = "", *, file=None, row_group=None,
                 column=None, page=None):
        super().__init__(message)
        self.message = message
        self.file = file
        self.row_group = row_group
        self.column = column
        self.page = page

    def coordinates(self) -> dict:
        """The known coordinates, as a dict (omits unknowns)."""
        return {
            k: getattr(self, k)
            for k in _COORD_FIELDS
            if getattr(self, k) is not None
        }

    def annotate(self, **coords) -> "ScanError":
        """Fill in *missing* coordinates; returns self for re-raise."""
        for k, v in coords.items():
            if k not in _COORD_FIELDS:
                raise TypeError(f"unknown coordinate {k!r}")
            if getattr(self, k) is None and v is not None:
                setattr(self, k, v)
        return self

    def __str__(self) -> str:
        c = self.coordinates()
        if not c:
            return self.message
        at = ", ".join(f"{k}={v}" for k, v in c.items())
        return f"{self.message} [{at}]"


class CorruptPageError(ScanError, ValueError):
    """One page's bytes are wrong (CRC mismatch, malformed header,
    truncated payload, impossible value counts)."""


class CorruptChunkError(ScanError, ValueError):
    """A column chunk is structurally wrong beyond one page (byte
    range out of bounds, short read, value-count mismatch)."""


class CorruptFooterError(ScanError, ValueError):
    """The file's framing or ``FileMetaData`` is wrong: bad magic, torn
    or truncated footer, thrift that does not decode, or metadata whose
    offsets/counts fail validation against the file
    (``format/validate.py``).  Carries the byte ``offset`` of the
    rejecting check (when one layer knows it) next to the usual scan
    coordinates, and the structured validator ``findings`` when the
    strict-metadata path raised it.  The legacy name
    ``tpuparquet.format.footer.FormatError`` is an alias."""

    def __init__(self, message: str = "", *, offset=None, findings=None,
                 **coords):
        super().__init__(message, **coords)
        self.offset = offset
        self.findings = list(findings) if findings else []

    def coordinates(self) -> dict:
        c = super().coordinates()
        if self.offset is not None:
            c["offset"] = self.offset
        return c


class CorruptManifestError(ScanError, ValueError):
    """A partitioned dataset's manifest (or commit journal) failed its
    framing checks: not the envelope format, unknown version, CRC
    mismatch over the canonical body, or a body that fails structural
    validation.  The dataset-level analogue of
    :class:`CorruptFooterError` — ``file`` carries the manifest path,
    and the resolver degrades to the newest *older* snapshot that
    validates (quarantining this one) rather than failing the scan."""


class TransientIOError(ScanError, OSError):
    """An I/O failure that may succeed on retry."""


class DeviceDispatchError(ScanError, RuntimeError):
    """Staging/dispatching decode work to the accelerator failed; the
    input bytes are fine and the CPU path can still decode them."""


class _DeadlineInfo:
    """Shared elapsed/budget plumbing for the two deadline classes
    (they must subclass *different* taxonomy parents — OSError for the
    retry ladder, RuntimeError for the dispatch ladder — so the info
    rides as a mixin)."""

    def _set_deadline(self, elapsed, budget, site):
        self.elapsed = elapsed   # seconds the operation actually ran
        self.budget = budget     # seconds it was allowed
        self.site = site         # watched site name (deadline.py)

    def _deadline_coords(self, c: dict) -> dict:
        if self.elapsed is not None:
            c["elapsed_s"] = round(self.elapsed, 3)
        if self.budget is not None:
            c["budget_s"] = self.budget
        return c


class DeadlineExceededError(_DeadlineInfo, TransientIOError):
    """A watched read ran past its time budget (hung NFS mount,
    stalled object-store request).  Subclasses
    :class:`TransientIOError`, so :func:`tpuparquet.faults.
    retry_transient` retries it and a quarantining scan absorbs the
    exhausted ladder — a hang becomes a bounded, classified failure
    instead of a stalled fleet."""

    def __init__(self, message: str = "", *, elapsed=None, budget=None,
                 site=None, **coords):
        super().__init__(message, **coords)
        self._set_deadline(elapsed, budget, site)

    def coordinates(self) -> dict:
        return self._deadline_coords(super().coordinates())


class DispatchDeadlineError(_DeadlineInfo, DeviceDispatchError):
    """A watched device dispatch ran past its time budget (wedged
    accelerator).  Subclasses
    :class:`DeviceDispatchError`, so the resilient read path's
    retry → CPU-fallback ladder handles it."""

    def __init__(self, message: str = "", *, elapsed=None, budget=None,
                 site=None, **coords):
        super().__init__(message, **coords)
        self._set_deadline(elapsed, budget, site)

    def coordinates(self) -> dict:
        return self._deadline_coords(super().coordinates())


class ServeStateError(RuntimeError):
    """Invalid scan-server lifecycle operation — e.g. activating a
    second process-wide :class:`~tpuparquet.serve.ResourceArbiter`
    while another is live.  A caller bug, not a scan failure: it
    never enters the quarantine/retry routing."""


class AdmissionRejected(ServeStateError):
    """Load-shed rejection from the scan server's admission control
    (:meth:`tpuparquet.serve.ResourceArbiter.admit`).

    Always RETRYABLE: the request was never queued, so resubmitting
    after ``retry_after_s`` is safe and duplicate-free.  Carries the
    machine-readable fields a client backoff loop needs: ``tenant``,
    ``reason`` (``"queue_full"`` / ``"byte_budget"`` /
    ``"deadline_budget"`` / ``"draining"``) and ``retry_after_s``."""

    def __init__(self, msg: str, *, tenant: str, reason: str,
                 retry_after_s: float):
        super().__init__(msg)
        self.tenant = tenant
        self.reason = reason
        self.retry_after_s = retry_after_s


# What a quarantining scan may absorb per unit: the library's clean
# failure taxonomy (ValueError covers Corrupt*/Thrift/codec errors,
# EOFError truncation, TypeError/NotImplementedError foreign shapes,
# OSError exhausted-retry I/O, RuntimeError exhausted device dispatch).
# Raw crash types (IndexError, KeyError, ...) are BUGS and always
# propagate — quarantine must never paper over them.  RecursionError
# subclasses RuntimeError, so catch sites pair this tuple with
# :func:`never_quarantine` to keep it (a crash, not a failure) loud.
QUARANTINE_ERRORS = (ValueError, EOFError, TypeError,
                     NotImplementedError, OSError, RuntimeError)


def never_quarantine(exc: BaseException) -> bool:
    """Crash types that must propagate even though they subclass a
    member of :data:`QUARANTINE_ERRORS`."""
    return isinstance(exc, RecursionError)
