"""Multi-file sharded scan driver.

The scan unit is (file, row-group) — the reference's outer loop
(``file_reader.go:51-57``) turned into a work list, sharded round-robin
over the mesh devices (SURVEY.md §5 "distributed communication backend").
Each unit decodes entirely on its assigned device via the kernel path;
cross-device exchange happens only at :func:`gather_column`, as one XLA
all-gather of the decoded column shards.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..errors import (
    QUARANTINE_ERRORS,
    DeadlineExceededError,
    never_quarantine,
)
from ..faults import QuarantineReport
from ..io.reader import FileReader
from ..obs import digest as _digest
from ..obs import profiler as _profiler
from ..obs import recorder as _flightrec
from ..obs import timeseries as _timeseries
from ..obs import trace as _trace
from ..obs.postmortem import postmortem_path_for, record_incident
from ..obs.recorder import flight
from ..kernels.decode import scatter_to_dense
from ..kernels.device import (
    DeviceColumn,
    pipelined_reads,
    read_row_group_device,
    read_row_group_device_resilient,
)

__all__ = ["ShardedScan", "scan_units", "open_sources",
           "resilient_unit_scan",
           "gather_column", "gather_byte_column",
           "save_cursor_file", "load_cursor_file", "host_cursor_path",
           "checkpoint_every_default"]


def scan_units(readers: list[FileReader], filter=None,
               verdicts: dict | None = None,
               pruned: list | None = None) -> list[tuple[int, int]]:
    """Flatten files into (file_index, row_group_index) work units.
    ``None`` entries (files quarantined at open time) contribute no
    units but keep the file-index space stable.

    With ``filter`` (a bound :mod:`tpuparquet.filter` expression), row
    groups the static verdict proves empty are DROPPED before units
    form — the scan never reads them.  Surviving verdicts land in
    ``verdicts`` (keyed ``(file, rg)``) so the per-unit decode reuses
    the candidate masks; dropped coordinates land in ``pruned`` as
    ``(file, rg, num_rows, reason, bloom_hits)`` for the driver's
    counters.  Deterministic given the footers, so every host of a
    multi-process scan derives the identical filtered unit list.

    The verdicts read the page-index / bloom blobs serially on the
    constructor's path — a handful of small seeks per filtered row
    group, fine for today's local seekable sources.  When remote
    object-store sources land (ROADMAP item 3), the page-index level
    should defer to the per-unit decode (pipelined + hedged) and unit
    forming should stop at the footer-only stats level."""
    units = []
    for fi, r in enumerate(readers):
        if r is None:
            continue
        for rgi in range(r.row_group_count()):
            if filter is not None:
                v = r.prune_row_group(filter, rgi)
                if v.skip:
                    if pruned is not None:
                        pruned.append(
                            (fi, rgi,
                             r.meta.row_groups[rgi].num_rows,
                             v.reason, v.bloom_hits))
                    if _flightrec._active is not None:
                        _flightrec.flight(
                            "row_group_pruned", site="shard.scan",
                            file=fi, row_group=rgi, reason=v.reason)
                    continue
                if verdicts is not None:
                    verdicts[(fi, rgi)] = v
            units.append((fi, rgi))
    return units


def _replicas(src) -> list:
    """A source entry is either one source or a replica group
    ``[primary, mirror, ...]`` of byte-identical copies."""
    if isinstance(src, (list, tuple)):
        if not src:
            raise ValueError("empty replica group in sources")
        return list(src)
    return [src]


def open_sources(sources, columns, *, on_error: str,
                 quarantine: QuarantineReport,
                 salvage: bool = False,
                 strict_metadata: bool | None = None,
                 record_for=None,
                 entry_extra: dict | None = None,
                 hedge_delay: float | None = None,
                 read_deadline: float | None = None,
                 postmortem: str | None = None) -> list:
    """Open scan sources with the file-level fault policy.

    Returns a reader list aligned with ``sources`` (``None`` where the
    file was quarantined).  Under ``on_error="raise"`` any open or
    strict-validation failure propagates — the seed behavior.  Under
    ``"quarantine"``, a failing file is isolated into ``quarantine``
    as a FILE-granularity entry and the scan proceeds without it; with
    ``salvage=True`` a failing file is first retried through the
    salvage path (its own hint, else the first healthy file as schema
    donor — every shard of a homogeneous dataset is a donor), keeping
    the recovered row-group prefix and quarantining only the torn
    remainder.  Salvage is deterministic, so every host of a
    multi-process scan derives the identical reader/unit list;
    ``record_for(i)`` optionally filters which file indices THIS
    process records (so fleet-folded counters count each file once).

    A source entry may be a replica group ``[primary, mirror, ...]``
    (byte-identical copies on independent stores): the first replica
    that OPENS becomes the reader and the others ride along as hedge
    mirrors for its chunk reads (``FileReader(mirrors=)``, the
    tail-at-scale path in ``deadline.py``); only if every replica
    fails to open is the file quarantined/salvaged.

    ``postmortem`` (a path or None) receives an automatic
    ``.postmortem.json`` incident for every file this call salvages or
    quarantines (:mod:`tpuparquet.obs.postmortem`), gated by the same
    ``record_for`` policy as the counters so a fleet writes each file's
    incident once.

    Raw crash types propagate — same contract as the unit loop.
    """
    from ..stats import current_stats

    if salvage and on_error != "quarantine":
        # under "raise" the first open failure aborts before any
        # salvage retry could run; accepting the kwarg would make the
        # explicit salvage request silently inert
        raise ValueError(
            "salvage=True requires on_error='quarantine' (under "
            "'raise' the first open failure aborts the scan)")

    readers: list = [None] * len(sources)
    failures: dict[int, BaseException] = {}
    donor = None

    def _record(i):
        return record_for is None or record_for(i)

    @contextlib.contextmanager
    def _counters_only_if_recorded(i):
        """FileReader increments files_salvaged / row_groups_recovered /
        metadata_rejects (and emits salvage/reject fault events)
        itself; on a multi-process scan every host opens every source,
        so for files this host does NOT record, roll the collector
        back — fleet-folded counters and event logs then count each
        file exactly once (matching the quarantine entries)."""
        st = current_stats()
        if st is None or _record(i):
            yield
            return
        # crc_mismatches/faults_injected too: the salvage forward scan
        # counts CRC rejects on every host that runs it
        fields = ("files_salvaged", "row_groups_recovered",
                  "metadata_rejects", "crc_mismatches",
                  "faults_injected")
        before = tuple(getattr(st, f) for f in fields)
        n_faults = len(st.events.faults) if st.events is not None \
            else None
        try:
            yield
        finally:
            for f, v in zip(fields, before):
                setattr(st, f, v)
            if n_faults is not None:
                del st.events.faults[n_faults:]

    from ..faults import retry_transient

    def _open_group(reps):
        """First replica that opens wins; the replicas NOT yet tried
        become its hedge mirrors (the ones that already failed to open
        are known-bad copies — hedging a read against them could let a
        truncated or diverged replica win the race).  All replicas
        failing re-raises the PRIMARY's error (the group's identity
        for quarantine purposes)."""
        first_err = None
        for j, rep in enumerate(reps):
            others = reps[j + 1:]
            try:
                # same retry policy as chunk reads: a flaky-store blip
                # at open time gets backoff before it can cost the
                # whole file (retry_transient re-raises non-transient
                # errors immediately)
                return retry_transient(lambda: FileReader(
                    rep, *columns, strict_metadata=strict_metadata,
                    mirrors=others, hedge_delay=hedge_delay,
                    read_deadline=read_deadline))
            except QUARANTINE_ERRORS as e:
                if never_quarantine(e):
                    raise
                if first_err is None:
                    first_err = e
        raise first_err

    for i, src in enumerate(sources):
        try:
            with _counters_only_if_recorded(i):
                readers[i] = _open_group(_replicas(src))
            if donor is None:
                donor = readers[i].meta
        except QUARANTINE_ERRORS as e:
            if never_quarantine(e) or on_error != "quarantine":
                raise
            failures[i] = e

    for i, err in sorted(failures.items()):
        primary = _replicas(sources[i])[0]
        path = primary if isinstance(primary, str) else None
        if path is not None:
            # a failing open may have been fed by (or may have seeded)
            # stale cached ranges: drop both tiers for this source so
            # the salvage retry below — and the next scan — reads the
            # store's truth, not the cache's memory of a bad file
            from ..io.rangecache import invalidate_source_caches

            invalidate_source_caches(path)
        if salvage:
            try:
                with _counters_only_if_recorded(i):
                    r = FileReader(primary, *columns, salvage=True,
                                   salvage_like=donor,
                                   strict_metadata=strict_metadata)
            except QUARANTINE_ERRORS as e2:
                if never_quarantine(e2):
                    raise
            else:
                readers[i] = r
                if _record(i):
                    extra = {"disposition": "salvaged",
                             "row_groups_recovered":
                                 r.row_group_count()}
                    if path is not None:
                        extra["path"] = path
                    if r.salvage_report:
                        for k in ("stop_reason", "bytes_lost"):
                            if k in r.salvage_report:
                                extra[k] = r.salvage_report[k]
                    entry = quarantine.add_file(file=i, error=err,
                                                **extra)
                    if entry_extra:
                        entry.update(entry_extra)
                    record_incident(postmortem, {
                        "kind": "file_salvaged",
                        "site": "shard.scan.file", **entry})
                continue
        if not _record(i):
            continue
        extra = {"disposition": "quarantined"}
        if path is not None:
            extra["path"] = path
        entry = quarantine.add_file(file=i, error=err, **extra)
        if entry_extra:
            entry.update(entry_extra)
        if _flightrec._active is not None:
            _flightrec.flight("file_quarantined",
                              site="shard.scan.file", **entry)
        record_incident(postmortem, {
            "kind": "file_quarantined", "site": "shard.scan.file",
            **entry})
        st = current_stats()
        if st is not None:
            st.files_quarantined += 1
            if st.events is not None:
                st.events.fault(site="shard.scan.file",
                                kind="file_quarantined", **entry)
    return readers


def cursor_state(units, next_key: str, next_value: int, **extra) -> dict:
    """Snapshot a JSON-serializable scan cursor (shared by ShardedScan
    and MultiHostScan so the format can't drift between them)."""
    cur = {"version": 1, next_key: next_value,
           "units": [list(u) for u in units]}
    cur.update(extra)
    return cur


def cursor_load(cursor: dict, units, next_key: str, n_units: int,
                **expected) -> int:
    """Validate a cursor against this scan's shape; returns the resume
    position.  ``expected`` pins run-identity fields (e.g. process grid
    coordinates) that must match exactly."""
    if cursor.get("version") != 1:
        raise ValueError(f"unknown cursor version {cursor.get('version')}")
    if [tuple(u) for u in cursor["units"]] != list(units):
        raise ValueError(
            "cursor does not match these sources: unit list differs "
            "(files changed since the cursor was taken?)"
        )
    for k, v in expected.items():
        if cursor.get(k) != v:
            raise ValueError(
                f"cursor {k} {cursor.get(k)!r} does not match this "
                f"run's {v!r}; resuming would misalign the unit "
                "assignment"
            )
    nxt = int(cursor[next_key])
    if not 0 <= nxt <= n_units:
        raise ValueError(f"cursor {next_key} {nxt} out of range")
    return nxt


# ----------------------------------------------------------------------
# Durable cursor checkpoints (crash-safe resume)
# ----------------------------------------------------------------------

CURSOR_FILE_FORMAT = "tpq-cursor"
CURSOR_FILE_VERSION = 1


def _canonical(obj) -> bytes:
    """The byte form the integrity checksum is computed over: key-
    sorted, separator-pinned JSON — identical before write and after a
    read-back round trip."""
    return json.dumps(obj, sort_keys=True,
                      separators=(",", ":")).encode()


def checkpoint_every_default() -> int:
    """Auto-checkpoint cadence in completed units
    (``TPQ_CHECKPOINT_EVERY``, default 16)."""
    try:
        v = int(os.environ.get("TPQ_CHECKPOINT_EVERY", ""))
    except ValueError:
        return 16
    return max(v, 1)


def save_cursor_file(cursor: dict, path: str) -> None:
    """Write a scan cursor durably and atomically.

    Versioned envelope with a CRC32 over the canonical cursor JSON;
    written tmp-in-same-dir + flush + fsync + ``os.replace`` +
    directory fsync — a SIGKILL at ANY point leaves either the
    previous complete checkpoint or the new complete checkpoint,
    never a torn one.  Counts ``DecodeStats.checkpoints_written``."""
    from ..stats import current_stats

    doc = {"format": CURSOR_FILE_FORMAT,
           "file_version": CURSOR_FILE_VERSION,
           "crc32": zlib.crc32(_canonical(cursor)),
           "cursor": cursor}
    d = os.path.dirname(os.path.abspath(path)) or "."
    tmp = os.path.join(
        d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    # fsync the directory so the rename itself is durable (best
    # effort: some filesystems refuse directory fds)
    try:
        dfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass
    st = current_stats()
    if st is not None:
        st.checkpoints_written += 1


def load_cursor_file(path: str) -> dict:
    """Read back a :func:`save_cursor_file` checkpoint, validating
    format, version, and integrity checksum.  Raises ``ValueError``
    on anything that is not a complete, untampered cursor (atomic
    writes mean a torn file here is damage, not a crash artifact)."""
    with open(path) as f:
        try:
            doc = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(
                f"checkpoint {path!r} is not valid JSON: {e}") from e
    if not isinstance(doc, dict) \
            or doc.get("format") != CURSOR_FILE_FORMAT:
        raise ValueError(f"{path!r} is not a tpq cursor checkpoint")
    if doc.get("file_version") != CURSOR_FILE_VERSION:
        raise ValueError(
            f"unknown checkpoint file_version "
            f"{doc.get('file_version')!r} in {path!r}")
    cursor = doc.get("cursor")
    if zlib.crc32(_canonical(cursor)) != doc.get("crc32"):
        raise ValueError(
            f"checkpoint {path!r} failed its integrity checksum")
    return cursor


def host_cursor_path(base: str, process_index: int) -> str:
    """Per-host checkpoint file for a multi-process scan: each process
    owns exactly one file (no cross-host write races)."""
    return f"{base}.p{process_index}"


def resilient_unit_scan(readers, units, device_for, *, start: int = 0,
                        retries=None, quarantine: QuarantineReport,
                        entry_extra: dict | None = None,
                        unit_deadline: float | None = None,
                        postmortem: str | None = None,
                        filter=None, verdicts=None):
    """The quarantine-mode unit loop shared by :class:`ShardedScan`
    and :class:`MultiHostScan`: decode each unit with the full
    resilience policy (transient-I/O retry, dispatch retry, CPU
    degradation); absorb clean failures into ``quarantine`` (entries
    get ``entry_extra`` merged in) and yield ``(k, None)`` for them so
    callers can advance their cursor uniformly; yield ``(k, out)`` for
    survivors.  Raw crash types propagate — quarantine never papers
    over a bug.

    ``unit_deadline`` bounds each unit's WHOLE decode (read + retries
    + dispatch + degradation) via the watchdog
    (:func:`~tpuparquet.deadline.call_with_deadline`): a unit that
    hangs past its budget raises
    :class:`~tpuparquet.errors.DeadlineExceededError`, which this loop
    absorbs into quarantine like any other exhausted failure — a hung
    unit costs its budget, never the fleet."""
    from ..deadline import call_with_deadline
    from ..stats import current_stats

    for k in range(start, len(units)):
        fi, rgi = units[k]
        # causal trace: the resilient path decodes one unit at a time
        # on the driving thread, so the unit span pushes the ambient
        # context — retry/degrade/deadline children (including the
        # deadline worker, which adopts this context) nest under it
        usp = _trace.open_span("unit", unit=k, file=fi,
                               row_group=rgi) \
            if _trace._active is not None else None

        def _decode(k=k, fi=fi, rgi=rgi):
            # default_device is thread-local; the deadline wrapper may
            # execute this on a worker thread, so enter it inside
            with jax.default_device(device_for(k)):
                return read_row_group_device_resilient(
                    readers[fi], rgi, retries=retries, filter=filter,
                    verdict=(None if verdicts is None
                             else verdicts.get((fi, rgi))))

        try:
            if unit_deadline:
                out = call_with_deadline(
                    _decode, unit_deadline, site="shard.scan.unit",
                    file=fi, row_group=rgi)
            else:
                out = _decode()
        except QUARANTINE_ERRORS as e:
            if never_quarantine(e):
                _trace.close_span(usp, status="error",
                                  error=type(e).__name__)
                raise
            entry = quarantine.add(unit=k, file=fi, row_group=rgi,
                                   error=e)
            if entry_extra:
                entry.update(entry_extra)
            # a quarantined unit means this file's bytes can no longer
            # be trusted against its footer: drop its cached plans so a
            # later retry (or another scan in this process) re-derives
            # them from the bytes it actually reads.  Only an
            # ALREADY-COMPUTED fingerprint can have entries — never
            # compute one here (fresh footer I/O on the possibly-wedged
            # handle that just got this unit quarantined)
            from ..kernels.plancache import invalidate_fingerprint

            cached = getattr(readers[fi], "cached_plan_fingerprint",
                             None)
            if cached is not None:
                invalidate_fingerprint(cached())
            # automatic post-mortem: the trigger's exact coordinates
            # plus the flight-recorder tail and a metrics snapshot,
            # dumped beside the durable cursor (obs/postmortem.py)
            flight("quarantined", site="shard.scan.unit", **entry)
            record_incident(postmortem, {
                "kind": "quarantined", "site": "shard.scan.unit",
                **entry})
            st = current_stats()
            if st is not None:
                st.units_quarantined += 1
                if st.events is not None:
                    st.events.fault(site="shard.scan.unit",
                                    kind="quarantined", **entry)
            _trace.close_span(usp, status="error", quarantined=True,
                              error=type(e).__name__)
            yield k, None
            continue
        except BaseException:
            # raw crash types propagate — but never with a leaked
            # ambient trace context
            _trace.close_span(usp, status="error")
            raise
        _trace.close_span(usp)
        yield k, out


class DurableScanMixin:
    """Durable-checkpoint + scan-budget + live-telemetry plumbing
    shared by :class:`ShardedScan` and
    :class:`~tpuparquet.shard.distributed.MultiHostScan` (so cadence
    and expiry semantics cannot drift between them).  Hosts provide
    ``state()``, ``_checkpoint_path``/``_checkpoint_every``/
    ``_since_checkpoint``, ``scan_deadline``/``_run_t0``,
    :meth:`_progress`, :meth:`_advance`, and :meth:`_unit_coords`."""

    def _progress(self) -> tuple[int, int]:
        raise NotImplementedError

    def _advance(self, k: int) -> None:
        """Move the cursor past unit ``k``."""
        raise NotImplementedError

    def _unit_coords(self, k: int) -> tuple[int, int]:
        """``(file_index, row_group_index)`` of this driver's unit k."""
        raise NotImplementedError

    def _init_durable(self, *, on_error, unit_deadline, scan_deadline,
                      resume, resume_from, checkpoint_every,
                      checkpoint_path, postmortem=None) -> None:
        """Validate and resolve the shared time/checkpoint knobs (one
        owner for both drivers; ``checkpoint_path`` is the resolved
        per-driver file — per-host for the multi-host scan).  Call
        BEFORE opening sources: a bad knob must fail cheap.

        ``postmortem``: where automatic incident dumps go — a path to
        set it explicitly, ``False`` to disable, None to derive
        (beside the checkpoint, else ``TPQ_POSTMORTEM_DIR``, else
        off) — see :func:`tpuparquet.obs.postmortem.postmortem_path_for`."""
        from ..deadline import scan_deadline_default, unit_deadline_default

        if unit_deadline is not None and on_error != "quarantine":
            raise ValueError(
                "unit_deadline requires on_error='quarantine' (an "
                "expired unit is absorbed by the quarantine ladder)")
        if resume is not None and resume_from is not None:
            raise ValueError("pass resume= or resume_from=, not both")
        # env defaults apply only where the knob is usable: the unit
        # deadline lives in the quarantine ladder
        self.unit_deadline = unit_deadline if unit_deadline is not None \
            else (unit_deadline_default()
                  if on_error == "quarantine" else None)
        self.scan_deadline = scan_deadline if scan_deadline is not None \
            else scan_deadline_default()
        self._checkpoint_path = checkpoint_path
        self._checkpoint_every = (checkpoint_every
                                  if checkpoint_every is not None
                                  else checkpoint_every_default())
        self._since_checkpoint = 0
        self._run_t0 = None
        # cooperative drain: request_stop() (any thread) makes the
        # drive loop exit cleanly at the next unit boundary with the
        # durable cursor flushed — the serve layer's graceful-drain
        # primitive, usable by any embedder
        self._stop = threading.Event()
        self.stopped = False
        self._postmortem_path = (
            postmortem if isinstance(postmortem, str)
            else None if postmortem is False
            else postmortem_path_for(checkpoint_path))

    # -- live telemetry (obs/: progress, registry, flight recorder) ------

    def _init_telemetry(self, n_units: int,
                        progress_export: str | None,
                        label: str) -> None:
        """Arm the always-on surfaces: the :class:`~tpuparquet.obs.
        progress.ScanProgress` (exported to ``progress_export`` /
        ``TPQ_PROGRESS_EXPORT`` for ``parquet-tool top``) and, when
        live metrics are enabled, a scan-lifetime ambient collector
        that meters units nobody wrapped in ``collect_stats()`` into
        the process metrics registry.  Call AFTER the unit list
        exists."""
        from ..obs.live import LiveFold, live_enabled
        from ..obs.progress import (
            ScanProgress,
            label_slug,
            progress_export_default,
        )
        from ..stats import DecodeStats

        if progress_export is not None:
            path = progress_export
        else:
            path = progress_export_default()
            if path and label != "scan":
                # the env default names ONE file: concurrent scans
                # with distinct labels get their own (same shape as
                # the multi-host .p<idx> suffix), so two scans never
                # interleave frames in one status file
                path = f"{path}.{label_slug(label)}"
        self.progress = ScanProgress(n_units, label=label,
                                     export=path or None)
        self._live_stats = DecodeStats() if live_enabled() else None
        self._live_fold = LiveFold()
        # per-scan-label attribution ledger (obs/attribution.py): fed
        # the SAME counter deltas the registry fold applies, so
        # sum-over-ledgers equals the registry totals exactly; gated
        # by the same live-metrics switch for that conservation
        from ..obs import attribution as _attribution

        self._ledger = (_attribution.ledger(label)
                        if live_enabled() else None)
        self._attr_fold = LiveFold()
        self._attr_src = None
        # scan-end trace export (TPQ_TRACE_EXPORT): per-label suffix
        # exactly like the progress file, so concurrent scans and the
        # multi-host drivers never clobber one shared export
        tpath = _trace.trace_export_default()
        if tpath and label != "scan":
            tpath = f"{tpath}.{label_slug(label)}"
        self._trace_export = tpath or None
        self._trace_ctx = None
        # scan-end profile export (TPQ_PROFILE_EXPORT): same per-label
        # suffixing as the trace file, for the same two reasons
        ppath = _profiler.profile_export_default()
        if ppath and label != "scan":
            ppath = f"{ppath}.{label_slug(label)}"
        self._profile_export = ppath or None
        # arm the time-series ring now if TPQ_TIMESERIES_DIR appeared
        # after import, so the scan-end flush below has somewhere to
        # land even for scans shorter than the exporter interval
        _timeseries.maybe_start_ring()

    def _finish_telemetry(self, t_scan: float, troot,
                          status: str) -> None:
        """Scan-end longitudinal feeds: the whole-scan latency into
        the quantile digest (with the trace id as exemplar) and one
        ``scan_end`` frame onto the time-series ring — so a scan
        shorter than the exporter interval still leaves history.
        Both off-by-default, one ``is None`` check each."""
        if _digest._active is not None:
            _digest.observe(
                self.progress.label, "scan",
                int((time.monotonic() - t_scan) * 1e6),
                trace=(troot["trace"] if troot is not None else None),
                status=status)
        if _timeseries._active is not None:
            _timeseries.tick("scan_end")

    def _adopted(self):
        """Context installing the scan's ambient collector for one
        bounded step — ONLY when the caller has no collector of their
        own (a user's ``collect_stats`` always wins, and its scope
        exit folds to the registry instead)."""
        from ..stats import adopt_stats, current_stats

        if self._live_stats is not None and current_stats() is None:
            return adopt_stats(self._live_stats)
        return contextlib.nullcontext()

    def _fold_live(self) -> None:
        """Incrementally fold the ambient collector's delta into the
        process registry (unit-boundary cadence: a Prometheus scrape
        mid-scan sees the units decoded so far) AND the same delta
        into this scan's attribution ledger — one delta, two exact
        sinks, so per-scan ledgers sum to the registry totals."""
        from ..stats import current_stats

        delta = None
        if self._live_stats is not None:
            delta = self._live_fold.fold(self._live_stats)
        # the profile brief rides the progress frame independently of
        # live metrics: `top` shows PROFILE whenever a sampler is armed
        if _profiler._active is not None:
            self.progress.set_profile(_profiler._active.brief())
        led = self._ledger
        if led is None:
            return
        st = current_stats() or self._live_stats
        if st is not None:
            if st is self._live_stats:
                attr_delta = delta or {}
            else:
                # a user collector shadows the ambient one: track its
                # deltas with a dedicated baseline fold (registry gets
                # the user scope's own fold at scope exit)
                if st is not self._attr_src:
                    from ..obs.live import LiveFold

                    self._attr_src = st
                    self._attr_fold = LiveFold()
                attr_delta = self._attr_fold.delta_only(st)
            if attr_delta:
                led.fold_delta(attr_delta)
        from ..kernels.arena import take_arena_peak

        led.note_peak(take_arena_peak())
        # the live surfaces see the same numbers: the progress frame
        # (parquet-tool top) carries the ledger's cpu_s/bytes view
        view = led.as_dict()
        self.progress.set_attribution({
            "cpu_s": view["cpu_s"],
            "bytes": view["bytes"],
            "peak_arena_bytes": led.peak_arena_bytes,
        })

    def _export_trace(self, troot) -> None:
        """Publish this trace at scan end (``TPQ_TRACE_EXPORT``, the
        per-label path resolved at init): the traced spans plus the
        process attribution ledgers, atomically — the file
        ``parquet-tool doctor`` walks.  Best-effort by contract."""
        if troot is None or self._trace_export is None:
            return
        tr = _trace._active
        if tr is None:
            return
        from ..obs.attribution import ledgers_snapshot
        from ..obs.export import write_trace_file

        write_trace_file(tr.snapshot(troot["trace"]),
                         self._trace_export,
                         ledgers=ledgers_snapshot(),
                         anchor=tr.anchor())

    def _export_profile(self) -> None:
        """Publish the sampling profile at scan end
        (``TPQ_PROFILE_EXPORT``, the per-label path resolved at
        init).  Independent of tracing: a profile without a trace is
        still a flamegraph.  Best-effort by contract."""
        p = _profiler._active
        if p is None or self._profile_export is None:
            return
        from ..obs.profiler import write_profile_file

        try:
            write_profile_file(p.to_state(), self._profile_export)
        except OSError:
            pass

    def _init_filter(self, filter, readers) -> None:
        """Shared filter plumbing: bind once against the (homogeneous)
        dataset schema, then let :func:`scan_units` prune row groups
        statically.  Call BEFORE forming units."""
        self.filter = filter
        self._verdicts: dict = {}
        self._pruned: list = []
        if filter is None:
            return
        from ..filter import bind_filter

        for r in readers:
            if r is not None:
                bind_filter(filter, r.schema)
                break

    def _count_pruned(self, select_pruned=None,
                      select_kept=None) -> None:
        """Fold the unit-forming pruning decisions into the active (or
        ambient) collector — called at RUN start, not construction, so
        ``run_with_stats``/``collect_stats`` wrappers see them.  The
        selectors filter which pruned entries / kept-unit verdicts
        THIS process records (multi-host: each row group counts once
        across the fleet)."""
        if self.filter is None:
            return
        from ..stats import current_stats

        with self._adopted():
            st = current_stats()
            if st is None:
                return
            hits = 0
            for j, (_fi, _rgi, n_rows, _reason, bh) in enumerate(
                    self._pruned):
                if select_pruned is not None and not select_pruned(j):
                    continue
                st.row_groups_pruned += 1
                st.rows_pruned += n_rows
                hits += bh
            # kept row groups' verdicts may also carry refuting probes
            # (an Or branch the bloom killed while another matched)
            for key, v in self._verdicts.items():
                if select_kept is not None and not select_kept(key):
                    continue
                hits += v.bloom_hits
            st.bloom_hits += hits

    def _drive(self, gen):
        """The shared unit loop around an inner unit generator
        (pipelined or resilient): progress ticks, ambient metering,
        registry folds, then the checkpoint/deadline bookkeeping —
        one owner for both drivers.  Yields ``(k, out)`` for units
        that decoded; quarantine-mode ``None`` results tick progress
        but are not yielded (the existing contract)."""
        from ..stats import current_stats

        prog = self.progress
        nxt0, n_total = self._progress()
        if prog.units_done != nxt0 or prog.state != "pending":
            # a fresh drive of an already-used progress: run() after a
            # partial run_iter (cursor reset to 0), a cursor resume
            # (resumed units count as already done), or CONTINUING a
            # stopped run_iter — all restart the clock and tallies, so
            # elapsed/rows_per_s describe this run, not the idle gap
            prog.restart(done=nxt0)
        prog.begin()
        # causal trace root: one trace per drive; the sampling verdict
        # is whole-trace, and every unit/stage span below parents into
        # this root's context (None = tracing off or unsampled)
        troot = None
        if _trace._active is not None:
            from ..kernels.device import _usable_cpus

            troot = _trace.start_trace(
                prog.label, units=n_total, resumed_at=nxt0,
                usable_cpus=_usable_cpus())
        self._trace_ctx = _trace.ctx_of(troot)
        if self._ledger is not None:
            self._ledger.scans += 1
        t_scan = time.monotonic()
        try:
            with self._adopted():
                self._check_scan_deadline()
            while True:
                if self._stop.is_set():
                    gen.close()
                    with self._adopted():
                        self._flush_checkpoint()
                    self._fold_live()
                    self.stopped = True
                    prog.finish("stopped")
                    self._finish_telemetry(t_scan, troot, "stopped")
                    _trace.end_trace(troot, status="cancelled")
                    self._export_trace(troot)
                    self._export_profile()
                    return
                nxt, _ = self._progress()
                prog.unit_started(nxt)
                t_unit = time.monotonic()
                try:
                    with self._adopted():
                        k, out = next(gen)
                except StopIteration:
                    prog.unit_cancelled(nxt)
                    break
                self._advance(k)
                fi, rgi = self._unit_coords(k)
                rows = (self.readers[fi].meta.row_groups[rgi].num_rows
                        if out is not None else 0)
                # staged bytes come from whichever collector actually
                # metered this unit: the caller's (a user collect_stats
                # scope shadows the ambient collector) or the ambient
                # one — else `top` would show staged 0 exactly on the
                # post-hoc-regime path
                st = current_stats() or self._live_stats
                prog.unit_done(
                    k, rows=rows, quarantined=out is None,
                    bytes_staged=(st.bytes_staged
                                  if st is not None else None))
                if _flightrec._active is not None:
                    _flightrec.flight(
                        "unit_done" if out is not None
                        else "unit_quarantined",
                        site="shard.scan", unit=k, file=fi,
                        row_group=rgi, rows=rows)
                if _digest._active is not None:
                    _digest.observe(
                        prog.label, "unit",
                        int((time.monotonic() - t_unit) * 1e6),
                        trace=(troot["trace"] if troot is not None
                               else None),
                        unit=k, file=fi, row_group=rgi)
                self._fold_live()
                if out is not None:
                    yield k, out
                with self._adopted():
                    self._maybe_checkpoint()
                    self._check_scan_deadline()
        except GeneratorExit:
            prog.finish("stopped")
            self._fold_live()
            self._finish_telemetry(t_scan, troot, "stopped")
            _trace.end_trace(troot, status="cancelled")
            self._export_trace(troot)
            self._export_profile()
            raise
        except BaseException:
            prog.finish("error")
            self._fold_live()
            self._finish_telemetry(t_scan, troot, "error")
            _trace.end_trace(troot, status="error")
            self._export_trace(troot)
            self._export_profile()
            raise
        with self._adopted():
            self._flush_checkpoint()
        self._fold_live()
        prog.finish("done")
        self._finish_telemetry(t_scan, troot, "done")
        _trace.end_trace(troot)
        self._export_trace(troot)
        self._export_profile()

    # -- consumer-aligned gathers (scan-level placement default) ---------

    def _gather_placement(self, out_sharding, gather_to):
        """An explicit per-call spec wins; else the scan-level default
        (which already folded the ``TPQ_GATHER_TO`` env).
        ``out_sharding="replicated"`` explicitly requests the seed
        replicated-ndarray gather even when a scan default is armed —
        None cannot express that (it means "use the default")."""
        if out_sharding is not None or gather_to is not None:
            from .mesh import resolve_out_sharding

            return resolve_out_sharding(self.mesh, out_sharding,
                                        gather_to)
        return self.out_sharding

    def gather_column(self, results, path: str, *, out_sharding=None,
                      gather_to=None):
        """:func:`gather_column` over this scan's mesh, defaulting to
        the placement the scan was constructed with
        (``out_sharding="replicated"`` forces the seed replicated
        gather past an armed default).  Runs under the scan's ambient
        collector and trace context, so gather counters land in this
        scan's attribution ledger and the gather span attaches to the
        scan's trace."""
        with self._adopted(), _trace.adopt(self._trace_ctx):
            out = gather_column(
                self.mesh, results, path,
                out_sharding=self._gather_placement(out_sharding,
                                                    gather_to))
        self._fold_live()
        return out

    def gather_byte_column(self, results, path: str, *,
                           out_sharding=None, gather_to=None):
        """:func:`gather_byte_column` over this scan's mesh,
        defaulting to the placement the scan was constructed with
        (``out_sharding="replicated"`` forces the seed replicated
        gather past an armed default).  Metered like
        :meth:`gather_column`."""
        with self._adopted(), _trace.adopt(self._trace_ctx):
            out = gather_byte_column(
                self.mesh, results, path,
                out_sharding=self._gather_placement(out_sharding,
                                                    gather_to))
        self._fold_live()
        return out

    def request_stop(self) -> None:
        """Ask a running :meth:`run_iter` to stop cooperatively: the
        drive loop exits BEFORE starting another unit, flushes the
        durable cursor (when checkpointing is configured), and marks
        progress/trace ``stopped`` — then sets :attr:`stopped` so the
        caller can distinguish a drain from completion.  Safe from
        any thread and before the run starts (the loop checks first);
        the serve layer's graceful-drain hook."""
        self._stop.set()

    def cursor_save(self, path: str | None = None) -> None:
        """Durably checkpoint :meth:`state` (atomic tmp + fsync +
        rename, integrity checksum — :func:`save_cursor_file`).
        ``path`` defaults to this scan's configured checkpoint
        file."""
        path = path if path is not None else self._checkpoint_path
        if path is None:
            raise ValueError("no checkpoint path: pass path= or "
                             "construct with resume_from=")
        save_cursor_file(self.state(), path)
        self._since_checkpoint = 0

    def _maybe_checkpoint(self) -> None:
        """Auto-checkpoint cadence: called once per completed unit
        AFTER the consumer's iteration step returned, so a unit is
        only ever covered by a checkpoint once the caller had its
        chance to persist the result — a crash re-decodes at most the
        units since the last checkpoint (bit-exact, so a keyed
        consumer converges to the identical union)."""
        if self._checkpoint_path is None:
            return
        self._since_checkpoint += 1
        if self._since_checkpoint >= self._checkpoint_every:
            self.cursor_save()

    def _flush_checkpoint(self) -> None:
        if self._checkpoint_path is not None and self._since_checkpoint:
            self.cursor_save()

    def _check_scan_deadline(self) -> None:
        """Whole-scan budget, checked between units: expiry flushes a
        fresh durable cursor (when checkpointing is on) and raises —
        the caller reschedules and resumes, no work is lost."""
        if not self.scan_deadline or self._run_t0 is None:
            return
        elapsed = time.monotonic() - self._run_t0
        if elapsed <= self.scan_deadline:
            return
        from ..deadline import record_expiry
        from ..stats import current_stats

        done, total = self._progress()
        record_expiry(current_stats(), "shard.scan", elapsed,
                      self.scan_deadline, {"next_unit": done})
        record_incident(self._postmortem_path, {
            "kind": "scan_deadline", "site": "shard.scan",
            "elapsed_s": round(elapsed, 3),
            "budget_s": self.scan_deadline, "next_unit": done,
            "units_total": total})
        self._flush_checkpoint()
        raise DeadlineExceededError(
            f"scan exceeded its {self.scan_deadline:g}s budget at "
            f"unit {done}/{total}; the cursor is intact — resume to "
            "continue",
            elapsed=elapsed, budget=self.scan_deadline,
            site="shard.scan")


class ShardedScan(DurableScanMixin):
    """Decode many files' row groups data-parallel across a mesh.

    ``sources`` are paths or file objects, opened by the scan itself
    (lazily tolerant — see :func:`open_sources`), so a corrupt FILE is
    a policy decision, not a constructor crash; ``columns`` optionally
    project.
    :meth:`run` decodes every unit on its round-robin device and returns
    per-unit ``{path: DeviceColumn}`` dicts; results stay device-resident
    and sharded until explicitly gathered.  Host planning of unit N+1
    overlaps device transfer of unit N
    (:func:`~tpuparquet.kernels.device.pipelined_reads`).

    Resumable (SURVEY.md §5 checkpoint/resume — the row group as the
    restart unit): :meth:`state` snapshots a cursor after any number of
    :meth:`run_iter` steps; pass it back as ``resume=`` to continue from
    the first undecoded unit in a fresh process.  The cursor is plain
    JSON-serializable data.

    Epoch shuffling (training loaders): ``shuffle_seed=`` +
    ``epoch=`` permute the unit list deterministically per epoch —
    identical on every host, applied before the cursor exists, so
    checkpoint/resume of a shuffled epoch stays duplicate-free (the
    cursor records the shuffle identity and refuses a mismatched
    resume).  With ``shuffle_seed=None`` (default) the natural order
    is untouched and ``epoch`` is ignored.

    Fault tolerance (``on_error``):

    * ``"raise"`` (default) — first failure aborts the scan, exactly
      the seed behavior, on the fully pipelined path.
    * ``"quarantine"`` — each unit decodes independently (transient
      I/O retried with backoff, device dispatch retried then degraded
      to the bit-exact CPU decode); a unit that still fails is
      isolated into :attr:`quarantine` (a
      :class:`~tpuparquet.faults.QuarantineReport` with exact
      file/row-group/column/page coordinates and the error class) and
      the scan continues.  Decoded units are bit-exact or absent —
      never wrong.  The cursor advances past quarantined units and
      carries the report, so a resumed scan neither re-decodes nor
      forgets them.  This mode trades the plan/transfer pipeline
      overlap for isolation (units decode one at a time).

    File-level policy (this round):

    * ``strict_metadata`` — validate every footer at open
      (``format/validate.py``); under ``"quarantine"`` a rejected file
      becomes a file-granularity quarantine entry instead of an abort.
    * ``salvage`` — auto-salvage-then-quarantine-remainder: a file
      whose footer is torn/invalid is reopened through the salvage
      path (``FileReader(salvage=True)``, schema donated by its hint
      or the first healthy file); its recovered row groups join the
      unit list, and only the unreadable remainder lands in
      :attr:`quarantine`.

    Time/crash domain (deadline round, ``tpuparquet/deadline.py``):

    * ``unit_deadline`` (env ``TPQ_UNIT_DEADLINE_S``; quarantine mode
      only) — watchdog budget per unit: a hung unit is abandoned and
      quarantined as :class:`~tpuparquet.errors.DeadlineExceededError`
      instead of stalling the scan.
    * ``scan_deadline`` (env ``TPQ_SCAN_DEADLINE_S``) — whole-scan
      budget, checked between units; expiry raises with the cursor
      intact so the caller reschedules and resumes.
    * replica groups + ``hedge_delay``/``read_deadline`` — a source
      may be ``[primary, mirror, ...]``; slow chunk reads hedge
      against the mirrors after the hedge delay (env
      ``TPQ_HEDGE_DELAY_S``, default rolling p95), first success wins.
    * ``resume_from=path`` + ``checkpoint_every`` (env
      ``TPQ_CHECKPOINT_EVERY``, default 16) — durable crash-safe
      cursor: the scan resumes from ``path`` when it exists and
      auto-checkpoints to it atomically as units complete, so a
      SIGKILL'd process resumes with no unit lost; re-decoded units
      (at most one checkpoint window) are bit-exact, so a keyed
      consumer converges to the identical union.  :meth:`cursor_save`
      checkpoints explicitly.

    Predicate pushdown (this round): ``filter=`` takes a
    :mod:`tpuparquet.filter` expression (``col("x") > 5``).  Row
    groups the chunk statistics / bloom filters / page index prove
    empty are dropped BEFORE units form (``row_groups_pruned``/
    ``rows_pruned``); surviving units decode late-materialized —
    filter columns first, exact predicate, only surviving rows of the
    other columns staged — so each yielded unit holds exactly the
    matching rows, bit-identical to a full scan post-filtered
    (``TPQ_PRUNE=0`` forces that reference path).  A cursor taken
    under one filter resumes only under the same filter (the unit
    list is part of the cursor's identity).

    Output placement (this round): ``out_sharding=`` (a
    ``NamedSharding`` over the consumer's mesh, or a ``PartitionSpec``
    over the scan mesh) or ``gather_to=`` (a single device, or its
    index in ``jax.local_devices()``; env default ``TPQ_GATHER_TO``)
    set the scan-level default placement for :meth:`gather_column` /
    :meth:`gather_byte_column` — decoded columns assemble directly
    onto the shards that will consume them instead of being
    all-gathered to every device (cost flat in mesh size for a
    singular consumer, proportional to true fan-out otherwise).
    Decode placement is unchanged (units still round-robin the scan
    mesh); only the gather's output layout moves.
    """

    def __init__(self, sources, *columns: str, mesh=None, resume=None,
                 on_error: str = "raise", retries: int | None = None,
                 salvage: bool = False,
                 strict_metadata: bool | None = None,
                 unit_deadline: float | None = None,
                 scan_deadline: float | None = None,
                 hedge_delay: float | None = None,
                 read_deadline: float | None = None,
                 resume_from: str | None = None,
                 checkpoint_every: int | None = None,
                 progress_export: str | None = None,
                 progress_label: str = "scan",
                 postmortem=None,
                 filter=None,
                 shuffle_seed: int | None = None, epoch: int = 0,
                 out_sharding=None, gather_to=None):
        from .mesh import make_mesh, resolve_out_sharding

        if on_error not in ("raise", "quarantine"):
            raise ValueError(
                f"on_error must be 'raise' or 'quarantine', "
                f"not {on_error!r}")
        self._init_durable(
            on_error=on_error, unit_deadline=unit_deadline,
            scan_deadline=scan_deadline, resume=resume,
            resume_from=resume_from, checkpoint_every=checkpoint_every,
            checkpoint_path=resume_from, postmortem=postmortem)
        self.mesh = mesh if mesh is not None else make_mesh()
        # resolve the scan-level placement default EARLY: a bad spec
        # must fail before any source opens
        self.out_sharding = resolve_out_sharding(
            self.mesh, out_sharding, gather_to)
        # file-level entries recorded at open time live in their own
        # report so run() can reset the unit-level entries without
        # forgetting the files that never produced units
        self._open_quarantine = QuarantineReport()
        self.readers = open_sources(
            sources, columns, on_error=on_error,
            quarantine=self._open_quarantine, salvage=salvage,
            strict_metadata=strict_metadata, hedge_delay=hedge_delay,
            read_deadline=read_deadline,
            postmortem=self._postmortem_path)
        self._init_filter(filter, self.readers)
        self.units = scan_units(self.readers, filter=self.filter,
                                verdicts=self._verdicts,
                                pruned=self._pruned)
        # epoch shuffling for training loaders: a deterministic
        # per-epoch permutation of the unit list, applied BEFORE any
        # cursor/telemetry sees the units — the cursor stores (and
        # resume validates) the permuted order, so a resumed epoch
        # stays duplicate-free, and every host derives the identical
        # permutation (string-seeded Random hashes with sha512, so
        # PYTHONHASHSEED cannot skew it).  ``shuffle_seed=None`` (the
        # default) leaves the natural file/row-group order untouched —
        # byte-identical to a scan without the feature, epoch ignored.
        self.shuffle_seed = shuffle_seed
        self.epoch = int(epoch)
        if shuffle_seed is not None:
            import random

            random.Random(
                f"{int(shuffle_seed)}:{self.epoch}").shuffle(self.units)
        # progress_label keys this scan's registry gauges (see
        # obs/progress.py): concurrent scans in one serve process pass
        # distinct labels so their gauges don't clobber each other
        self._init_telemetry(len(self.units), progress_export,
                             progress_label)
        self.devices = list(self.mesh.devices.flat)
        self.on_error = on_error
        self.retries = retries
        self.quarantine = QuarantineReport(
            self._open_quarantine.as_dicts())
        self._next_unit = 0
        if resume is None and resume_from is not None \
                and os.path.exists(resume_from):
            resume = load_cursor_file(resume_from)
        if resume is not None:
            self._load_cursor(resume)

    def _load_cursor(self, cursor: dict) -> None:
        expected = {}
        if self.shuffle_seed is not None:
            # shuffle identity is part of the cursor: resuming under a
            # different seed/epoch would re-decode or skip units
            expected["shuffle"] = [int(self.shuffle_seed), self.epoch]
        self._next_unit = cursor_load(cursor, self.units, "next_unit",
                                      len(self.units), **expected)
        self.quarantine = QuarantineReport.from_dicts(
            cursor.get("quarantine"))
        # the resumed scan re-opened its sources, so a file already
        # quarantined in the checkpointed cursor was rejected AGAIN at
        # open time — merge the fresh open entries deduped by
        # coordinates instead of double-listing the file
        self.quarantine.merge_unique(self._open_quarantine.as_dicts())

    def state(self) -> dict:
        """JSON-serializable cursor: resume with
        ``ShardedScan(sources, ..., resume=state)``.  Valid between
        :meth:`run_iter` steps; decoding restarts at the first unit not
        yet yielded.  Quarantined units ride along (coordinates +
        error class), so a resumed scan's report stays complete."""
        extra = {}
        if self.shuffle_seed is not None:
            extra["shuffle"] = [int(self.shuffle_seed), self.epoch]
        return cursor_state(self.units, "next_unit", self._next_unit,
                            quarantine=self.quarantine.as_dicts(),
                            **extra)

    def device_for(self, unit_index: int):
        return self.devices[unit_index % len(self.devices)]

    def _progress(self) -> tuple[int, int]:
        return self._next_unit, len(self.units)

    def _advance(self, k: int) -> None:
        self._next_unit = k + 1

    def _unit_coords(self, k: int) -> tuple[int, int]:
        return self.units[k]

    def run_iter(self):
        """Yield ``(unit_index, {path: DeviceColumn})`` from the cursor
        position, advancing it after each unit.  In quarantine mode,
        failed units are skipped (recorded in :attr:`quarantine`), so
        the yielded unit indices identify exactly what decoded.

        With ``resume_from=`` configured the cursor auto-checkpoints
        durably every ``checkpoint_every`` completed units (and at
        scan end); with ``scan_deadline`` set the scan stops between
        units once the budget is spent, raising
        :class:`~tpuparquet.errors.DeadlineExceededError` with the
        cursor intact.

        Fail-fast scans (``on_error="raise"``) run in the one unit
        window, :func:`~tpuparquet.kernels.device.pipelined_reads`,
        filtered or not; quarantine mode decodes unit by unit in
        :func:`resilient_unit_scan`.

        Live telemetry (this round): :attr:`progress` ticks at every
        unit boundary (``parquet-tool top`` watches the exported
        status file), units decode under the scan's ambient collector
        when the caller has none (so the always-on metrics registry
        moves mid-scan), and quarantine/deadline events dump automatic
        post-mortems beside the durable cursor."""
        self._run_t0 = time.monotonic()
        if self.filter is not None and self._next_unit == 0:
            # fresh run: fold the unit-forming prune decisions exactly
            # once (a cursor resume already counted them in its run)
            self._count_pruned()
        if self.on_error == "raise":
            gen = pipelined_reads(
                self.readers, self.units, self.device_for,
                start=self._next_unit, filter=self.filter,
                verdicts=self._verdicts)
        else:
            gen = resilient_unit_scan(
                self.readers, self.units, self.device_for,
                start=self._next_unit, retries=self.retries,
                quarantine=self.quarantine,
                unit_deadline=self.unit_deadline,
                postmortem=self._postmortem_path,
                filter=self.filter, verdicts=self._verdicts)
        yield from self._drive(gen)

    def run(self) -> list[dict[str, DeviceColumn]]:
        """Decode ALL units (position i of the result is unit i).

        Always a full scan — the cursor resets to the start first, so a
        resumed instance cannot return a dense list whose positions
        silently stop matching unit indices (``gather_column`` et al.
        index results positionally).  To continue a partial scan from a
        cursor, use :meth:`run_iter`, which labels each result with its
        unit index.

        In quarantine mode the list holds only the units that decoded
        (fewer, never wrong); :attr:`quarantine` names the missing ones
        by exact coordinates, and :meth:`run_iter` labels survivors
        with their true unit indices for positional consumers."""
        self._next_unit = 0
        if self.on_error == "quarantine":
            self.quarantine = QuarantineReport(
                self._open_quarantine.as_dicts())
        return [out for _, out in self.run_iter()]

    def run_with_stats(self, events: bool = False):
        """:meth:`run` under a fresh collector; returns
        ``(results, stats)``.  ``events=True`` attaches the per-page
        event log (``stats.events``) — the single-process counterpart
        of ``MultiHostScan.run_with_stats``, whose fleet aggregate
        (``shard.distributed.allgather_stats``) folds exactly these
        collectors across hosts."""
        from ..stats import collect_stats

        with collect_stats(events=events) as st:
            results = self.run()
        return results, st

    def close(self):
        for r in self.readers:
            if r is not None:
                r.close()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()


def gather_column(mesh, results: list[dict[str, DeviceColumn]], path: str,
                  *, out_sharding=None, gather_to=None):
    """Gather one fixed-width column across the mesh, placed where the
    consumer wants it.

    Builds a (U, L, lanes) global array sharded unit-wise over the "rg"
    axis from the per-device results (null slots zero-filled, units
    padded to a common length L), then reshards it to the requested
    output placement:

    * default (no spec) — replicate everywhere: one jitted identity
      whose replicated out-sharding XLA lowers to the all-gather
      collective over ICI.  Returns ``(values (U, L, lanes) ndarray,
      per-unit true counts)`` — the seed contract, unchanged.  (The
      ``TPQ_GATHER_TO`` env default applies at the SCAN level —
      ``ShardedScan(gather_to=)`` and the scan's gather methods — not
      here: an env knob must not silently change this function's
      return type under existing callers.)
    * ``out_sharding=`` (a ``NamedSharding`` over the consumer's mesh,
      or a ``PartitionSpec`` over the scan mesh) / ``gather_to=`` (a
      single device) — assemble directly onto the shards that will
      consume the column instead of all-gathering every byte to every
      device.  Cost is flat in mesh size for a singular consumer and
      proportional only to true fan-out otherwise.  Returns a
      device-resident ``jax.Array`` of shape (U', L, lanes) under the
      requested sharding, where U' pads the unit axis up to the
      spec's unit-axis partition count (rows ``>= len(counts)`` are
      zero); slice with the counts as usual.

    Placement resolution (and the mesh-mismatch errors) live in
    :func:`~tpuparquet.shard.mesh.resolve_out_sharding`.  The phase is
    metered: ``DecodeStats.gather_bytes_moved`` / ``_replicated`` /
    ``gather_reshard_s`` decompose what the reshard actually shipped.
    """
    from .mesh import resolve_out_sharding

    placement = resolve_out_sharding(mesh, out_sharding, gather_to,
                                     env_default=False)
    cols = [r[path] for r in results]
    if any(c.offsets is not None for c in cols):
        raise TypeError("gather_column handles fixed-width columns; "
                        "use gather_byte_column for BYTE_ARRAY")
    lanes = cols[0].lanes if cols else 1
    if any(c.lanes != lanes for c in cols):
        raise TypeError("gather_column units disagree on value width")
    # flat (num_values*lanes,) per unit: device buffers stay 1-D (a 2-D
    # (n, lanes) stack would tile T(8,128) on TPU — 64x HBM padding)
    dense = [
        scatter_to_dense(c.data, c.mask, c.positions, lanes=lanes)
        for c in cols
    ]
    counts = np.asarray([c.num_values for c in cols], dtype=np.int64)
    L = int(counts.max()) if len(counts) else 0
    padded = [jnp.pad(d.astype(jnp.uint32), (0, L * lanes - d.shape[0]))
              for d in dense]
    (gathered,), perm = _assemble_and_gather(
        mesh, [(padded, (L * lanes,), jnp.uint32)],
        placement=placement, out_row_shapes=[(L, lanes)])
    if placement is not None:
        return gathered, counts
    # host-side reshape to the (U, L, lanes) view callers index; the
    # shard-major assembly order un-permutes here
    out = np.asarray(gathered).reshape(gathered.shape[0], L, lanes)
    return out[perm[: len(dense)]], counts


def _count_gather(arrays, placement) -> None:
    """Meter one gather's reshard outcome: what each destination shard
    actually received (``gather_bytes_moved``), how much of that was
    pure replication beyond one copy of each global byte
    (``gather_bytes_replicated``).  Exact integers off the output
    shardings — no estimation."""
    from ..stats import current_stats

    st = current_stats()
    if st is None and _flightrec._active is None:
        return
    moved = extra = 0
    for a in arrays:
        nb = int(np.prod(a.shape, dtype=np.int64)) * a.dtype.itemsize
        per = int(np.prod(a.sharding.shard_shape(a.shape),
                          dtype=np.int64)) * a.dtype.itemsize
        tot = per * len(a.sharding.device_set)
        moved += tot
        extra += max(0, tot - nb)
    if st is not None:
        st.gather_bytes_moved += moved
        st.gather_bytes_replicated += extra
    if _flightrec._active is not None:
        _flightrec.flight(
            "gather", site="shard.scan.gather", streams=len(arrays),
            bytes_moved=moved, bytes_replicated=extra,
            placement=("replicated" if placement is None
                       else repr(placement)))


def _assemble_and_gather(mesh, streams, placement=None,
                         out_row_shapes=None):
    """Reshard per-unit device arrays into globals under the requested
    output placement, WITHOUT funneling them through a single device.

    The naive route (``jnp.stack`` then ``device_put`` with the sharded
    layout) materializes the whole global on ONE device before the
    reshard — on a real mesh that is a full extra trip over PCIe/ICI
    for every byte, and it serializes on device 0 (the worst overhead
    found by ``tools/scan_scale_curve.py``).  Instead: stack each rg
    block's units on the block's own device (units were placed
    round-robin, so rows are grouped shard-major), assemble each global
    zero-copy with :func:`jax.make_array_from_single_device_arrays`,
    then reshard in ONE step:

    * ``placement is None`` — one jitted identity over all streams
      whose replicated out-shardings lower to the all-gather
      collectives (the seed behavior, byte-identical).
    * ``placement`` (a resolved ``Sharding``) — one jitted
      permute-to-unit-order whose out-shardings ARE the consumer's
      spec, so each destination shard receives exactly its rows (plus
      the spec's true fan-out); when the target's device set differs
      from the mesh's (a single device, a consumer sub-mesh), the
      assembled globals hop via ``jax.device_put`` resharding first —
      still one data-sized move, never an all-gather.

    ``streams`` is a list of ``(padded_units, row_shape, dtype)`` — all
    streams must have the same unit count.  ``out_row_shapes``
    optionally reshapes each placed stream's rows (placed outputs
    cannot reshape host-side).  Returns ``(arrays, perm)``: with no
    placement, ``arrays[i]`` holds the unit at shard-major row i and
    ``perm`` maps unit index -> row; with a placement, ``arrays[i]``
    is already unit-ordered (rows past the true unit count are zero)
    and ``perm`` still maps unit -> shard-major assembly row.
    """
    # generalize over mesh rank: an rg-only mesh (no "sp" axis) is the
    # sp == 1 layout — callers may build their own 1-D mesh
    n_rg = mesh.shape["rg"]
    sp = dict(mesh.shape).get("sp", 1)
    grid = np.asarray(mesh.devices).reshape(n_rg, sp)
    n_dev = n_rg * sp
    n_true = len(streams[0][0])
    U = max(((n_true + n_dev - 1) // n_dev) * n_dev, n_dev)
    t_parts = 1
    if placement is not None:
        from .mesh import dim0_partitions

        # the assembled global may itself hop through a device_put
        # reshard to the target, so its unit axis must divide by the
        # target's unit-axis partition count too (jax requires
        # divisible shardings)
        t_parts = dim0_partitions(placement)
        while U % t_parts:
            U += n_dev
        if placement.device_set != set(mesh.devices.flat) \
                and _dim0_only(placement):
            # consumer outside the scan mesh (single sink device,
            # consumer sub-mesh): skip the shard-major global — each
            # unit row goes point-to-point to its destination shard,
            # once.  The whole step is the reshard.
            from ..stats import current_stats

            st = current_stats()
            t0 = time.perf_counter()
            # stage hint: keep sampled gather time inside the same
            # window the span times (doctor cross-checks the two)
            ptok = _profiler.stage_begin("gather") \
                if _profiler._active is not None else None
            try:
                out = _assemble_direct(placement, streams, n_true,
                                       t_parts, out_row_shapes)
                jax.block_until_ready(out)
            finally:
                if ptok is not None:
                    _profiler.stage_end(ptok)
            t1 = time.perf_counter()
            if st is not None:
                st.gather_reshard_s += t1 - t0
            if _trace._active is not None:
                _trace.emit_span("gather", t0, t1 - t0,
                                 streams=len(out), direct=True)
            _count_gather(out, placement)
            return list(out), np.arange(n_true, dtype=np.int64)
    rows_per_block = U // n_rg
    order = []   # shard-major: unit index per gathered row
    # P("rg") shards rows over rg only: rg block r spans the units the
    # round-robin placed on its sp sibling devices, and the whole block
    # replicates across those siblings
    blocks = [
        [u for u in range(n_true)
         if r * sp <= (u % n_dev) < (r + 1) * sp]
        for r in range(n_rg)
    ]
    for r, mine in enumerate(blocks):
        order.extend(mine)
        order.extend([-1] * (rows_per_block - len(mine)))
    stacked_all = []
    for padded, row_shape, dtype in streams:
        zero = None
        shards = []  # one per device, grid order (sp fastest)
        for r, mine in enumerate(blocks):
            # explicit placement BEFORE the stack: rows of an sp > 1
            # block live on sibling devices, and a jitted stack over
            # mixed committed devices is backend-dependent (no-op
            # transfer when the scan already placed the unit here)
            rows = [jax.device_put(padded[u], grid[r, 0]) for u in mine]
            if len(rows) < rows_per_block:
                if zero is None:
                    zero = np.zeros(row_shape, dtype=dtype)
                rows += [zero] * (rows_per_block - len(rows))
            block = jnp.stack(rows)
            for s in range(sp):
                shards.append(jax.device_put(block, grid[r, s]))
        sharding = NamedSharding(mesh, P("rg"))
        global_shape = (U,) + tuple(shards[0].shape[1:])
        stacked_all.append(jax.make_array_from_single_device_arrays(
            global_shape, sharding, shards))
    # perm[u] = shard-major assembly row of unit u
    perm = np.empty(n_true, dtype=np.int64)
    for row, u in enumerate(order):
        if u >= 0:
            perm[u] = row
    from ..stats import current_stats

    st = current_stats()
    t0 = time.perf_counter()
    ptok = _profiler.stage_begin("gather") \
        if _profiler._active is not None else None
    try:
        if placement is None:
            rep = NamedSharding(mesh, P())
            out = jax.jit(
                lambda *xs: xs,
                out_shardings=tuple(rep for _ in stacked_all)
            )(*stacked_all)
        else:
            out = _place_streams(mesh, stacked_all, placement, perm,
                                 n_true, t_parts, out_row_shapes)
        jax.block_until_ready(out)
    finally:
        if ptok is not None:
            _profiler.stage_end(ptok)
    t1 = time.perf_counter()
    if st is not None:
        st.gather_reshard_s += t1 - t0
    if _trace._active is not None:
        _trace.emit_span("gather", t0, t1 - t0, streams=len(out),
                         placement=("replicated" if placement is None
                                    else "placed"))
    _count_gather(out, placement)
    return list(out), perm


def _place_streams(mesh, stacked, placement, perm, n_true: int,
                   t_parts: int, out_row_shapes):
    """The consumer-aligned reshard: permute the shard-major assembly
    rows back to unit order INSIDE the placing computation, so the
    collective and the un-permute are one step and no byte detours
    through the host.  Output unit axis pads to a multiple of the
    target's partition count (rows >= ``n_true`` zeroed)."""
    u_out = ((max(n_true, 1) + t_parts - 1) // t_parts) * t_parts
    rows = np.zeros(u_out, dtype=np.int64)
    rows[:n_true] = perm
    valid = (np.arange(u_out) < n_true)
    shapes = [tuple(x.shape[1:]) if out_row_shapes is None
              else tuple(out_row_shapes[i])
              for i, x in enumerate(stacked)]

    def place(*xs):
        outs = []
        for x, shp in zip(xs, shapes):
            y = x[rows]
            mask = valid.reshape((u_out,) + (1,) * (y.ndim - 1))
            y = jnp.where(mask, y, jnp.zeros((), dtype=y.dtype))
            outs.append(y.reshape((u_out,) + shp))
        return tuple(outs)

    specs = tuple(placement for _ in stacked)
    if placement.device_set == set(mesh.devices.flat):
        # same device set: the permute + reshard compile as one
        # program; XLA emits exactly the collectives the spec implies
        return jax.jit(place, out_shardings=specs)(*stacked)
    # different device set (single consumer device, consumer
    # sub-mesh): hop the assembled shards to the target layout first —
    # one data-sized reshard, flat in mesh size — then permute locally
    # on the consumer's devices.  (Reached only for specs that shard
    # more than the unit axis; dim0-only specs take the cheaper direct
    # assembly in _assemble_and_gather and never build `stacked`.)
    # The hop carries only the spec's UNIT-axis partitioning: the
    # assembled intermediates are flat 2-D (U, row) — the full spec
    # describes the reshaped outputs and would mis-rank (or
    # mis-divide) against them; the jit below applies it.
    if isinstance(placement, NamedSharding):
        spec = placement.spec
        hop = NamedSharding(placement.mesh,
                            P(spec[0] if len(spec) else None))
    else:
        hop = placement
    moved = [jax.device_put(x, hop) for x in stacked]
    return jax.jit(place, out_shardings=specs)(*moved)


def _dim0_only(placement) -> bool:
    """Does this placement shard nothing beyond the unit axis?  (The
    precondition for direct per-destination assembly: a unit's whole
    row then lives on each of its destination devices.)"""
    if isinstance(placement, NamedSharding):
        spec = placement.spec
        return all(spec[i] is None for i in range(1, len(spec)))
    return True  # SingleDeviceSharding


def _assemble_direct(placement, streams, n_true: int, t_parts: int,
                     out_row_shapes):
    """Point-to-point assembly for consumer targets OUTSIDE the scan
    mesh's device set (a single sink device, a consumer sub-mesh):
    each unit's padded row hops straight to its destination shard(s)
    and stacks there in unit order.  The data moves exactly once per
    destination copy — true fan-out only, no collective, no permute,
    no intermediate global.  Requires a dim-0-only spec
    (:func:`_dim0_only`); rows >= ``n_true`` are zero."""
    u_out = ((max(n_true, 1) + t_parts - 1) // t_parts) * t_parts
    outs = []
    for i, (padded, row_shape, dtype) in enumerate(streams):
        shp = tuple(row_shape) if out_row_shapes is None \
            else tuple(out_row_shapes[i])
        gshape = (u_out,) + shp
        zero = None
        shards = []
        for dev, idx in placement.devices_indices_map(gshape).items():
            sl = idx[0]
            start = sl.start or 0
            stop = u_out if sl.stop is None else sl.stop
            rows = []
            for u in range(start, stop):
                if u < n_true:
                    rows.append(jax.device_put(padded[u], dev))
                else:
                    if zero is None:
                        zero = np.zeros(row_shape, dtype=dtype)
                    rows.append(jax.device_put(zero, dev))
            block = jnp.stack(rows).reshape((stop - start,) + shp)
            shards.append(jax.device_put(block, dev))
        outs.append(jax.make_array_from_single_device_arrays(
            gshape, placement, shards))
    return tuple(outs)


def gather_byte_column(mesh, results: list[dict[str, DeviceColumn]],
                       path: str, *, out_sharding=None, gather_to=None):
    """Gather one BYTE_ARRAY column across the mesh, placed where the
    consumer wants it.

    Each unit's shard densifies on its own device first: null record
    slots become zero-length values (their bytes are already absent, so
    the packed data buffer IS the dense data buffer — only the offsets
    re-derive), then padded (offsets to Lmax+1 with the byte total,
    keeping them monotone; data to Bmax with zeros) and stacked into
    (U, Lmax+1) / (U, Bmax) globals sharded unit-wise over "rg",
    resharded to the requested placement exactly like
    :func:`gather_column` (same ``out_sharding=``/``gather_to=``
    semantics, same default-replicated seed contract, same counters).

    Returns ``(offsets (U, Lmax+1), data (U, Bmax) u8, row_counts,
    byte_counts)``; row i of unit u spans
    ``data[u, offsets[u, i]:offsets[u, i+1]]``.  Offsets are PER-UNIT
    relative (each row's offsets start at 0), which makes them
    placement-invariant: a destination shard holds matching
    (offsets, data) rows, so the rebase is already per-destination-
    shard and no global offset rebase is needed under any spec.  With
    a placement the two returned arrays are device-resident
    ``jax.Array``\\ s whose unit axis pads to the spec's partition
    count (rows ``>= len(row_counts)`` zero) and whose dim-0
    shardings match, row for row.
    """
    from .mesh import resolve_out_sharding

    placement = resolve_out_sharding(mesh, out_sharding, gather_to,
                                     env_default=False)
    cols = [r[path] for r in results]
    if any(c.offsets is None for c in cols):
        raise TypeError("gather_byte_column handles BYTE_ARRAY columns; "
                        "use gather_column for fixed-width types")
    dense_offs = []
    datas = []
    for c in cols:
        offs = c.offsets[: c.n_packed + 1]
        lens = offs[1:] - offs[:-1]
        if c.num_values == c.n_packed and c._mask_p is None:
            dl = lens
        else:
            dl = jnp.where(c.mask, lens[c.positions],
                           jnp.zeros((), dtype=lens.dtype))
        do = jnp.concatenate(
            [jnp.zeros((1,), dtype=lens.dtype), jnp.cumsum(dl)]
        )
        dense_offs.append(do)
        datas.append(c.data)
    row_counts = np.asarray([d.shape[0] - 1 for d in dense_offs],
                            dtype=np.int64)
    byte_counts = np.asarray([d.shape[0] for d in datas], dtype=np.int64)
    L = int(row_counts.max()) + 1 if len(cols) else 1
    B = max(int(byte_counts.max()), 1) if len(cols) else 1
    # pad each unit on its own device (edge-padding keeps the offsets
    # monotone at the byte total), then assemble shard-major and
    # all-gather without funneling through one device
    offs_dtype = dense_offs[0].dtype if cols else jnp.int32
    offs_padded = [jnp.pad(do, (0, L - do.shape[0]), mode="edge")
                   for do in dense_offs]
    data_padded = [jnp.pad(d, (0, B - d.shape[0])) for d in datas]
    (o_g, d_g), perm = _assemble_and_gather(
        mesh, [(offs_padded, (L,), offs_dtype),
               (data_padded, (B,), jnp.uint8)],
        placement=placement)
    if placement is not None:
        return o_g, d_g, row_counts, byte_counts
    return (np.asarray(o_g)[perm[: len(cols)]],
            np.asarray(d_g)[perm[: len(cols)]],
            row_counts, byte_counts)
