"""Multi-host scan driver (SURVEY.md §5 "distributed communication
backend": multi-file scans shard (file x row-group) work lists across
processes via ``jax.distributed``).

Each process decodes its slice of the global work list on its local
devices (ICI-parallel via :class:`~tpuparquet.shard.scan.ShardedScan`);
cross-host exchange uses the XLA collectives JAX places on DCN.  All
entry points degrade to single-process behavior, so the same driver
script runs unchanged from a laptop to a pod.
"""

from __future__ import annotations

import os
import time

import jax
import numpy as np

__all__ = [
    "initialize",
    "process_units",
    "MultiHostScan",
    "allgather_host",
    "allgather_bytes",
    "allgather_stats",
    "allgather_metrics",
    "allgather_digests",
    "allgather_profiles",
]

from .scan import DurableScanMixin as _DurableScanMixin  # noqa: E402


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Bring up the JAX distributed runtime (no-op if single-process
    args are absent and the environment provides no cluster config).

    Mirrors the reference's absent-but-implied multi-process story: the
    runtime handles barrier/NCCL-equivalent transport; we only shard
    work lists."""
    if coordinator_address is None and num_processes is None:
        return  # single process; jax.process_count() == 1
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def process_units(units, process_index: int | None = None,
                  process_count: int | None = None) -> list:
    """This process's slice of the global (file, row-group) work list.

    Strided assignment (unit i -> process i % P): deterministic across
    processes with no coordination, and balanced when row-group sizes
    are i.i.d. — the same policy the in-process scan uses per device."""
    p = jax.process_index() if process_index is None else process_index
    n = jax.process_count() if process_count is None else process_count
    return [u for i, u in enumerate(units) if i % n == p]


def allgather_host(local_rows: np.ndarray) -> np.ndarray:
    """All-gather variable host arrays across processes (DCN).

    Single-process: identity.  Multi-process: delegates to
    ``jax.experimental.multihost_utils.process_allgather``.

    64-bit payloads ship as (lo, hi) u32 lanes: JAX's default 32-bit
    mode silently truncates int64/float64 in transit — checksums over
    2**32 came back wrapped (caught by the at-scale two-process run;
    the framework's device buffers use the same lane convention)."""
    a = np.asarray(local_rows)
    if jax.process_count() == 1:
        return a
    from jax.experimental import multihost_utils

    if a.dtype.itemsize == 8:
        a1 = np.atleast_1d(a)  # 0-d arrays refuse the itemsize re-view
        lanes = np.ascontiguousarray(a1).view(np.uint32).reshape(
            a1.shape + (2,))
        out = np.asarray(multihost_utils.process_allgather(lanes))
        res = np.ascontiguousarray(out).view(a.dtype).reshape(
            out.shape[:-1])
        if a.ndim == 0:  # drop the atleast_1d axis: (procs, 1) -> (procs,)
            res = res.reshape(res.shape[0])
        return res
    return np.asarray(multihost_utils.process_allgather(a))


def allgather_bytes(payload: bytes) -> list[bytes]:
    """All-gather one variable-length byte payload per process.

    Two collectives: lengths first (so every process can pad to the
    common maximum — ``process_allgather`` requires identical shapes),
    then the padded u8 buffers.  Single-process: ``[payload]``."""
    if jax.process_count() == 1:
        return [payload]
    lens = allgather_host(np.asarray(len(payload), dtype=np.int64))
    lens = lens.reshape(-1)
    L = max(int(lens.max()), 1)
    buf = np.zeros(L, dtype=np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    out = allgather_host(buf).reshape(len(lens), L)
    return [out[i, : int(lens[i])].tobytes() for i in range(len(lens))]


def allgather_stats(st) -> "DecodeStats":
    """Fold every host's ``DecodeStats`` — counters AND log2-bucket
    histograms — into one fleet-wide collector, identical on every
    process (rank 0 reports it; the others get it for free, the
    all-gather is symmetric).

    Counters ship EXACT (``to_state``, not the display-rounded
    ``as_dict``) as JSON over :func:`allgather_bytes`, so the fleet
    totals equal the elementwise sum of the per-host counters and the
    fleet histograms are the exact bucket-wise sums (the
    ``obs.Histogram`` merge property).  ``wall_s`` folds as the MAX
    across hosts — the hosts decode concurrently, so the fleet
    values/sec is fleet values over the slowest host's wall, not over
    the summed walls.  Per-page event logs stay host-local (per-page
    detail does not aggregate; export it per host instead)."""
    import json

    from ..stats import DecodeStats

    payloads = allgather_bytes(json.dumps(st.to_state()).encode())
    total = DecodeStats()
    wall = 0.0
    for p in payloads:
        host = DecodeStats.from_state(json.loads(p))
        total.merge_from(host)
        wall = max(wall, host.wall_s)
    total.wall_s = wall
    return total


def allgather_metrics(reg=None) -> "MetricsRegistry":
    """Fold every host's live metrics registry
    (:mod:`tpuparquet.obs.live`) into one fleet-wide registry,
    identical on every process — the always-on counterpart of
    :func:`allgather_stats`, same wire (exact JSON state over
    :func:`allgather_bytes`), same exactness: fleet counters are the
    elementwise sums and fleet histograms the exact bucket-wise sums
    of the per-host registries, so the merged snapshot equals the
    single-host snapshot of the union corpus.  Gauges are
    instantaneous, not cumulative — each host's land under a
    ``p<idx>_`` prefix instead of being summed.  ``reg`` defaults to
    this process's registry."""
    import json as _json

    from ..obs.live import MetricsRegistry, registry

    if reg is None:
        reg = registry()
    payloads = allgather_bytes(_json.dumps(reg.to_state()).encode())
    total = MetricsRegistry()
    for i, p in enumerate(payloads):
        state = _json.loads(p)
        gauges = state.pop("gauges", {}) or {}
        total.merge_from(MetricsRegistry.from_state(state))
        for k, v in gauges.items():
            total.gauge(f"p{i}_{k}", v)
    return total


def allgather_digests(reg=None) -> "DigestRegistry":
    """Fold every host's latency quantile digests
    (:mod:`tpuparquet.obs.digest`) into one fleet-wide registry,
    identical on every process — same wire as
    :func:`allgather_metrics` (exact JSON state over
    :func:`allgather_bytes`), same exactness: the digests' fixed
    sub-octave buckets sum elementwise, so the merged digest equals
    the single-host digest of the union corpus bucket-for-bucket
    (what the soak harness pins).  ``reg`` defaults to this process's
    active digest registry; an unarmed process contributes an empty
    state."""
    import json as _json

    from ..obs.digest import DigestRegistry, digests

    if reg is None:
        reg = digests()
    state = {} if reg is None else reg.to_state()
    payloads = allgather_bytes(_json.dumps(state).encode())
    total = DigestRegistry()
    for p in payloads:
        total.merge_state(_json.loads(p))
    return total


def allgather_traces(spans=None) -> list[dict]:
    """Fold every host's causal-trace spans
    (:mod:`tpuparquet.obs.trace`) into one fleet-wide span list,
    identical on every process — the tracing sibling of
    :func:`allgather_metrics` (same wire: JSON over
    :func:`allgather_bytes`).  Each span gains a ``proc`` field naming
    its origin process (trace ids already embed the origin pid, so
    merged trees never collide); parent/child links are host-local by
    construction and survive the merge untouched.  ``spans`` defaults
    to this process's tracer snapshot ([] when tracing is off —
    the merge then returns only the hosts that traced)."""
    import json as _json

    from ..obs.trace import snapshot_spans

    if spans is None:
        spans = snapshot_spans()
    payloads = allgather_bytes(_json.dumps(spans).encode())
    merged: list[dict] = []
    for i, p in enumerate(payloads):
        for s in _json.loads(p):
            s["proc"] = i
            merged.append(s)
    merged.sort(key=lambda s: (s.get("proc", 0), s.get("t0", 0.0)))
    return merged


def allgather_profiles(state=None) -> dict:
    """Fold every host's sampling-profile state
    (:mod:`tpuparquet.obs.profiler`) into one fleet-wide profile,
    identical on every process — same wire as
    :func:`allgather_digests` (exact JSON state over
    :func:`allgather_bytes`), same exactness: sample counters and
    per-(label, stage) stack tallies sum elementwise, so the merged
    profile equals the single-host profile of the union sample set
    bucket-for-bucket.  ``state`` defaults to this process's armed
    profiler; an unarmed process contributes an empty state."""
    import json as _json

    from ..obs import profiler as _profiler
    from ..obs.profiler import merge_profile_states

    if state is None:
        p = _profiler.profiler()
        state = p.to_state() if p is not None else None
    payloads = allgather_bytes(
        _json.dumps(state or {}).encode())
    return merge_profile_states(
        [_json.loads(pl) for pl in payloads])


def allgather_ledgers() -> dict:
    """Fold every host's per-scan attribution ledgers
    (:mod:`tpuparquet.obs.attribution`) into one fleet-wide
    ``{label: ScanLedger}``, identical on every process: counters sum
    label-wise (exact — the merged ledger equals the single-host
    ledger of the union corpus), peaks fold as max (per-host arena
    occupancy is concurrent, not additive)."""
    import json as _json

    from ..obs.attribution import ledgers_state, merge_ledger_states

    payloads = allgather_bytes(_json.dumps(ledgers_state()).encode())
    return merge_ledger_states([_json.loads(p) for p in payloads])


class MultiHostScan(_DurableScanMixin):
    """Decode many files across processes *and* local devices.

    The global unit list (file x row-group) is strided over processes;
    each process runs a local :class:`ShardedScan`-style loop over its
    units on its own mesh.  ``run`` returns this process's decoded
    units; ``counts_allgather`` exchanges per-unit row counts so every
    process knows the global shape (the usual precursor to a global
    reshard).

    ``on_error="quarantine"`` isolates failing units per host instead
    of aborting the fleet (coordinates + error class in
    :attr:`quarantine`, same semantics as
    :class:`~tpuparquet.shard.scan.ShardedScan`); files whose footer
    fails to open/validate are quarantined (or, with ``salvage=True``,
    salvaged to their readable prefix) at FILE granularity — see
    :func:`~tpuparquet.shard.scan.open_sources`;
    :meth:`allgather_quarantine` folds every host's report into the
    fleet-wide list.

    Time/crash domain (same knobs as ``ShardedScan``):
    ``unit_deadline``/``scan_deadline`` bound hung units and the whole
    scan.  CAUTION — ``scan_deadline`` is evaluated PER HOST on its
    local clock and raises non-collectively: a host whose units finish
    under budget never raises, so a caller that follows ``run_iter``
    with a collective (``allgather_quarantine``, ``allgather_stats``,
    a gather) must reach that collective on EVERY host — catch
    ``DeadlineExceededError`` and fall through to the collective (the
    cursor is already checkpointed), or exchange a done/expired flag
    first; letting one host exit while its siblings enter the
    collective stalls the fleet.  Sources may be replica groups hedged
    after ``hedge_delay``;
    ``resume_from=base`` checkpoints durably to a PER-HOST file
    (``base.p<process_index>`` —
    :func:`~tpuparquet.shard.scan.host_cursor_path`, so hosts never
    race on one file) and resume validates fleet agreement: every
    host must see the same unit list and the same
    have-a-checkpoint answer, or the resume raises instead of
    silently re-decoding or skipping a shard.

    Output placement: ``out_sharding=``/``gather_to=`` (env
    ``TPQ_GATHER_TO``) set this PROCESS's default for
    :meth:`gather_column`/:meth:`gather_byte_column` — each host
    gathers its own local units onto its local target (the spec must
    be fully addressable from the process; cross-host exchange stays
    with the DCN collectives above).  Semantics otherwise identical to
    :class:`~tpuparquet.shard.scan.ShardedScan`."""

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
                 postmortem=None,
                 filter=None,
                 out_sharding=None, gather_to=None):
        from ..faults import QuarantineReport
        from ..obs.progress import progress_export_default
        from .mesh import make_mesh, resolve_out_sharding
        from .scan import (
            host_cursor_path,
            load_cursor_file,
            open_sources,
            scan_units,
        )

        if on_error not in ("raise", "quarantine"):
            raise ValueError(
                f"on_error must be 'raise' or 'quarantine', "
                f"not {on_error!r}")
        p0 = jax.process_index()
        self._init_durable(
            on_error=on_error, unit_deadline=unit_deadline,
            scan_deadline=scan_deadline, resume=resume,
            resume_from=resume_from, checkpoint_every=checkpoint_every,
            checkpoint_path=(None if resume_from is None
                             else host_cursor_path(resume_from, p0)),
            postmortem=postmortem)
        # every process opens every source (salvage is deterministic,
        # so all hosts derive the identical reader/unit list), but a
        # failed/salvaged FILE is recorded by exactly one process
        # (index mod grid) so fleet-folded counters and the
        # allgathered quarantine count each file once
        p, n = jax.process_index(), jax.process_count()
        self._open_quarantine = QuarantineReport()
        self.readers = open_sources(
            sources, columns, on_error=on_error,
            quarantine=self._open_quarantine, salvage=salvage,
            strict_metadata=strict_metadata,
            record_for=lambda i: i % n == p,
            entry_extra={"process_index": p},
            hedge_delay=hedge_delay, read_deadline=read_deadline,
            postmortem=self._postmortem_path)
        # pruning verdicts are a pure function of the footers, so every
        # host derives the identical filtered unit list (the same
        # determinism contract salvage relies on)
        self._init_filter(filter, self.readers)
        self.global_units = scan_units(self.readers, filter=self.filter,
                                       verdicts=self._verdicts,
                                       pruned=self._pruned)
        self.local_units = process_units(self.global_units)
        # per-host status file (base.p<idx>, like the checkpoints) so
        # hosts never race on one progress file; parquet-tool top takes
        # several paths and renders the fleet side by side.  The path
        # is fully resolved HERE ("" when disabled — never None, which
        # _init_telemetry would re-default from the env without the
        # per-host suffix)
        pe = (progress_export if progress_export is not None
              else progress_export_default())
        self._init_telemetry(
            len(self.local_units),
            (f"{pe}.p{p0}" if pe and n > 1 else pe) or "",
            f"scan.p{p0}")
        # make_mesh defaults to LOCAL devices (see its docstring; the
        # 2-process integration test caught the global-devices variant)
        self.mesh = mesh if mesh is not None else make_mesh()
        # scan-level output placement default (per PROCESS: each host
        # gathers its own units onto its local target — the resolver
        # rejects non-addressable specs; see resolve_out_sharding)
        self.out_sharding = resolve_out_sharding(
            self.mesh, out_sharding, gather_to)
        self.devices = list(self.mesh.devices.flat)
        self.on_error = on_error
        self.retries = retries
        self.quarantine = QuarantineReport(
            self._open_quarantine.as_dicts())
        self._next_local = 0
        if resume is None and self._checkpoint_path is not None:
            found = os.path.exists(self._checkpoint_path)
            if n > 1:
                self._validate_resume_agreement(found)
            if found:
                resume = load_cursor_file(self._checkpoint_path)
        if resume is not None:
            self._load_cursor(resume)

    def _validate_resume_agreement(self, found: bool) -> None:
        """Collective resume sanity: every host must derive the same
        global unit list AND give the same have-a-checkpoint answer.
        A host resuming while a sibling starts fresh would silently
        re-decode (or a diverged unit list silently misassign) its
        stride of the fleet's work — fail loudly instead."""
        import json
        import zlib

        units_crc = zlib.crc32(json.dumps(
            [list(u) for u in self.global_units]).encode())
        payloads = allgather_bytes(json.dumps(
            {"found": bool(found), "units_crc": units_crc}).encode())
        states = [json.loads(b) for b in payloads]
        crcs = {s["units_crc"] for s in states}
        if len(crcs) > 1:
            raise ValueError(
                "checkpoint resume: hosts disagree on the scan's unit "
                "list (sources changed on some hosts?)")
        founds = {s["found"] for s in states}
        if len(founds) > 1:
            missing = [i for i, s in enumerate(states)
                       if not s["found"]]
            raise ValueError(
                "checkpoint resume: only some hosts have a checkpoint "
                f"file (missing on process(es) {missing}); restore the "
                "missing per-host file(s) or delete them all to start "
                "fresh")

    def _load_cursor(self, cursor: dict) -> None:
        from ..faults import QuarantineReport
        from .scan import cursor_load

        # process grid coordinates are identity: a cursor restored on
        # the wrong process (or grid size) would silently skip or
        # re-decode units of the strided assignment
        self._next_local = cursor_load(
            cursor, self.global_units, "next_local_unit",
            len(self.local_units),
            process_count=jax.process_count(),
            process_index=jax.process_index(),
        )
        self.quarantine = QuarantineReport.from_dicts(
            cursor.get("quarantine"))
        # dedup against the re-opened sources' fresh file entries —
        # same fix as ShardedScan._load_cursor
        self.quarantine.merge_unique(self._open_quarantine.as_dicts())

    def state(self) -> dict:
        """JSON-serializable per-process cursor (resume with
        ``MultiHostScan(sources, ..., resume=state)`` on the SAME
        process of the SAME grid).  Valid between :meth:`run_iter`
        steps; carries this host's quarantine report."""
        from .scan import cursor_state

        return cursor_state(
            self.global_units, "next_local_unit", self._next_local,
            process_count=jax.process_count(),
            process_index=jax.process_index(),
            quarantine=self.quarantine.as_dicts(),
        )

    def _progress(self):
        return self._next_local, len(self.local_units)

    def _advance(self, k: int) -> None:
        self._next_local = k + 1

    def _unit_coords(self, k: int) -> tuple[int, int]:
        return self.local_units[k]

    def run_iter(self):
        """Yield ``(local_index, {path: DeviceColumn})`` from the cursor
        position, advancing it after each unit.  Quarantine mode skips
        (and records) failing units, like ``ShardedScan.run_iter``;
        the durable per-host checkpoint, the scan budget, and the live
        telemetry (per-host :attr:`progress` status file, ambient
        metrics, automatic post-mortems) apply exactly as there."""
        from ..kernels.device import pipelined_reads
        from .scan import resilient_unit_scan

        self._run_t0 = time.monotonic()
        if self.filter is not None and self._next_local == 0:
            # each dropped row group / kept verdict counts on exactly
            # one host, so the fleet-folded counters stay exact
            p, n = jax.process_index(), jax.process_count()
            local = set(self.local_units)
            self._count_pruned(
                select_pruned=lambda j: j % n == p,
                select_kept=lambda key: key in local)
        if self.on_error == "raise":
            gen = pipelined_reads(
                self.readers, self.local_units,
                lambda i: self.devices[i % len(self.devices)],
                start=self._next_local, filter=self.filter,
                verdicts=self._verdicts)
        else:
            gen = resilient_unit_scan(
                self.readers, self.local_units,
                lambda i: self.devices[i % len(self.devices)],
                start=self._next_local, retries=self.retries,
                quarantine=self.quarantine,
                entry_extra={"process_index": jax.process_index()},
                unit_deadline=self.unit_deadline,
                postmortem=self._postmortem_path,
                filter=self.filter, verdicts=self._verdicts)
        yield from self._drive(gen)

    def allgather_quarantine(self) -> list[dict]:
        """Every host's quarantine entries, identical on every process
        (JSON over :func:`allgather_bytes`, like the stats fold)."""
        import json

        payloads = allgather_bytes(
            json.dumps(self.quarantine.as_dicts()).encode())
        out: list[dict] = []
        for p in payloads:
            out.extend(json.loads(p))
        return out

    def run(self) -> list[dict]:
        """Decode ALL of this process's units (position i of the result
        is local unit i; always a full scan — resume via run_iter).

        Host planning of unit N+1 overlaps device transfer of unit N
        (same pipeline as :class:`~tpuparquet.shard.scan.ShardedScan`).
        In quarantine mode the result holds only the units that
        decoded; :attr:`quarantine` names the rest."""
        from ..faults import QuarantineReport

        self._next_local = 0
        if self.on_error == "quarantine":
            self.quarantine = QuarantineReport(
                self._open_quarantine.as_dicts())
        return [out for _, out in self.run_iter()]

    def run_with_stats(self, events: bool = False):
        """Decode ALL of this process's units under a collector and
        aggregate across the fleet.

        Returns ``(results, fleet, local)``: this process's decoded
        units (as :meth:`run`), the fleet-wide
        :class:`~tpuparquet.stats.DecodeStats` (identical on every
        process — ``fleet.summary()`` is the pod-level throughput
        line), and this process's own collector (which carries the
        per-page event log when ``events=True``; events stay
        host-local by design)."""
        from ..stats import collect_stats

        with collect_stats(events=events) as local:
            results = self.run()
        return results, allgather_stats(local), local

    def counts_allgather(self) -> np.ndarray:
        """(global_units,) row counts, identical on every process."""
        counts = np.zeros(len(self.global_units), dtype=np.int64)
        p = jax.process_index()
        n = jax.process_count()
        for j, (fi, rgi) in enumerate(self.global_units):
            if j % n == p:
                counts[j] = self.readers[fi].meta.row_groups[rgi].num_rows
        if n == 1:
            return counts
        return allgather_host(counts).reshape(n, -1).sum(axis=0)
