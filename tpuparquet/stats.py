"""Decode statistics and tracing (SURVEY.md §5 "metrics / logging").

The reference exposes introspection only through footer metadata; the
TPU build adds first-class decode-throughput counters — the BASELINE
metric (values/sec/chip) as a library feature:

    with tpuparquet.collect_stats() as st:
        reader.read_row_group_arrays(0)
    print(st.summary())

Counters are plain Python ints collected only while a collector is
active (zero overhead otherwise).  ``trace()`` wraps a scope in a JAX
profiler trace for TensorBoard.

THREAD-LOCAL SEMANTICS: the active collector is per-thread, not
per-process.  ``collect_stats()`` registers its collector on the
calling thread only — decode work an external caller dispatches to its
OWN worker threads inside the scope is invisible to that collector
unless each worker wraps its slice in :func:`worker_stats` and the
coordinator folds the result with ``merge_from`` after joining (the
pattern the library's internal thread pools use — see
``kernels/device.pipelined_reads`` and ``io/writer._flush_prepared``).
A shared collector incremented from racing threads would lose counts;
the thread-local design makes that impossible rather than unlikely.

Structured telemetry (``tpuparquet/obs/``) rides the same collector:
``collect_stats(events=True)`` attaches a per-page
:class:`~tpuparquet.obs.events.EventLog`, and log2-bucket histograms
(:class:`~tpuparquet.obs.histogram.Histogram`) record whenever any
collector is active.  Both merge exactly across ``worker_stats``
collectors and across hosts (``shard.distributed.allgather_stats``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

__all__ = ["DecodeStats", "collect_stats", "current_stats",
           "worker_stats", "merge_worker_stats", "adopt_stats",
           "trace"]


@dataclasses.dataclass
class DecodeStats:
    """Counters for one collection scope."""

    row_groups: int = 0
    chunks: int = 0
    pages: int = 0
    # pages whose values segment decompressed ON DEVICE (snappy token
    # kernel) rather than on host — evidence the device path engaged
    pages_device_snappy: int = 0
    # pages whose PLAIN values shipped as the byte-plane RLE transport
    # (upper planes as runs) instead of raw bytes
    pages_device_planes: int = 0
    # pages whose PLAIN int values shipped as packed delta offsets
    # (first + per-page min_delta + w-bit deltas), rebuilt by the delta
    # expand kernels — the sorted-column transport
    pages_device_delta_lanes: int = 0
    # write-side pages whose values encoded ON DEVICE (DeviceValues:
    # DELTA/BSS/PLAIN in kernels/encode.py) — evidence the writer TPU
    # path engaged rather than pulling raw values to host
    pages_device_encoded: int = 0
    # pages whose VALUES were decoded on host and staged as-is (the
    # catch-all else of the device dispatch, kernels/device.py) — the
    # fallback-matrix observable: tests/test_fallback_matrix.py pins
    # exactly which (encoding x type) land here, so a regression that
    # silently demotes a device path to host fails a test, not a profile
    pages_host_values: int = 0
    values: int = 0
    bytes_compressed: int = 0
    bytes_uncompressed: int = 0
    # chunk bytes fetched from the source (FileReader.chunk_blob —
    # in-memory views and file reads alike) and the wall spent
    # fetching them (retry/hedge/deadline wait included): the
    # read-side pair of plan_s/transfer_s, and the bytes_read half of
    # the per-scan attribution ledger (obs/attribution.py)
    bytes_read: int = 0
    read_s: float = 0.0
    # bytes shipped host->device THROUGH THE BATCHED STAGER (counted at
    # transfer time, split/padding included) — the transfer-wall
    # observable: compressed-wire shipping shows up as bytes_staged <
    # bytes_uncompressed.  A few fallback paths (CPU-decoded values,
    # FLBA/boolean staging inside finish()) transfer outside the
    # stager and are not counted here.
    bytes_staged: int = 0
    # arrays handed to jax.device_put by the batched stager (each split
    # piece counts, a fused chunk's stacked buffer counts once): with
    # bytes_staged, whether staging is bound by bytes or by the number
    # of puts
    pieces_staged: int = 0
    # page arrays of fused chunks that shipped inside a stacked buffer
    # (one piece per group and role) rather than as pieces of their own
    stacked_arrays: int = 0
    # device programs each column's finish() enqueued: one per chunk
    # decoded by a chunk program (chunks_fused counts those chunks,
    # pages_fused their data pages); on the per-page path each page
    # kernel, slice, concatenate and validity program
    chunks_fused: int = 0
    pages_fused: int = 0
    programs_dispatched: int = 0
    # dictionary BYTE_ARRAY data pages decoded on the device, fused or
    # per page, and those whose dictionary entries all have one length
    # (gathered as rows of a (D, length) view, with no offsets)
    dict_bytes_pages: int = 0
    dict_bytes_fixed_pages: int = 0
    # slow-path executions that a healthy build would run natively (e.g.
    # a stale .so forcing the numpy bp-stats fallback): nonzero means
    # perf has quietly regressed with no functional symptom
    native_fallbacks: int = 0
    # -- fault-tolerance observables (tpuparquet/faults.py, errors.py) --
    # pages whose header carried a CRC that was checked and matched;
    # mismatches raise CorruptPageError AND count, so a fleet report
    # can say "N pages verified, M rejected"
    pages_crc_verified: int = 0
    crc_mismatches: int = 0
    # injected faults delivered by the harness (tests/chaos drills only;
    # nonzero in production means an injector leaked)
    faults_injected: int = 0
    # transient-I/O retry attempts (faults.retry_transient) and
    # device-dispatch retry attempts (read_row_group_device_resilient)
    io_retries: int = 0
    dispatch_retries: int = 0
    # graceful degradation: pages planned under the forced-host decode
    # (transport "host-degraded") and whole units that fell back to the
    # bit-exact CPU decode after device dispatch kept failing
    pages_degraded: int = 0
    units_degraded: int = 0
    # scan units isolated by on_error="quarantine" (coordinates live in
    # the scan's QuarantineReport; this is the fleet-foldable total)
    units_quarantined: int = 0
    # -- file-level salvage observables (format/validate.py, recover.py) --
    # whole files whose footer was torn/invalid and were opened through
    # the salvage path (readable row-group prefix only), and the row
    # groups those salvages recovered
    files_salvaged: int = 0
    row_groups_recovered: int = 0
    # whole files a sharded scan quarantined at open time (footer
    # unusable and salvage off/failed); per-file coordinates live in
    # the scan's QuarantineReport
    files_quarantined: int = 0
    # footers rejected by strict metadata validation
    # (FileReader(strict_metadata=True) / TPQ_STRICT_METADATA)
    metadata_rejects: int = 0
    # -- time-domain observables (tpuparquet/deadline.py) --
    # watched operations (chunk reads, device dispatches, whole units)
    # that ran past their budget and were converted into
    # DeadlineExceededError/DispatchDeadlineError by the watchdog path
    deadline_exceeded: int = 0
    # hedged reads: extra replica reads launched after the hedge
    # delay, and how many of those actually won the race (a healthy
    # store hedges rarely and wins rarely; a degraded primary shows
    # hedges_won ~ hedges_issued)
    hedges_issued: int = 0
    hedges_won: int = 0
    # durable cursor checkpoints written (shard.scan.save_cursor_file
    # via the auto-checkpoint path or an explicit cursor_save)
    checkpoints_written: int = 0
    # -- write pipeline (io/pages.py, io/chunk.py) --
    # every page this scope wrote (dictionary + data, native or pure
    # path) and the subset whose body was assembled by the native
    # one-pass pipeline (native/page.c): the conservation invariant is
    # pages_assembled_native <= pages_written, with equality on data
    # pages when TPQ_WRITE_NATIVE is on and the codec qualifies
    pages_written: int = 0
    pages_assembled_native: int = 0
    # where the native write wall went, accumulated per page: body
    # encode (levels + dict-index/value streams into the arena
    # buffer), block compress + page CRC, and header build + buffer
    # writes.  All zero on the pure path (its stages interleave through
    # Python bytes and can't be attributed exactly).
    write_encode_s: float = 0.0
    write_compress_s: float = 0.0
    write_assemble_s: float = 0.0
    # block-parallel codec split: sub-blocks compressed as independent
    # frames on write (compress.page_compress_into) and frames decoded
    # concurrently on read (multi-frame ZSTD bodies).  Zero whenever
    # pages stay single-frame — the 1-worker byte-parity mode.
    codec_split_blocks: int = 0
    codec_split_frames: int = 0
    # -- predicate pushdown / pruning (tpuparquet/filter.py) --
    # row groups skipped entirely by a filter verdict (chunk Statistics,
    # bloom filters, or the page index proving no row can match) — the
    # scan never forms/decodes a unit for them
    row_groups_pruned: int = 0
    # data pages skipped inside surviving row groups (not decompressed,
    # not decoded, not staged), summed over column chunks
    pages_pruned: int = 0
    # rows statically eliminated by pruning decisions: the rows of
    # pruned row groups plus, per surviving filtered row group, the
    # rows outside the page-index candidate set (counted once per row
    # group, NOT once per column)
    rows_pruned: int = 0
    # bloom-filter probes that answered "definitely absent" (each such
    # verdict licenses a prune; blooms have no false negatives)
    bloom_hits: int = 0
    # -- partitioned datasets (tpuparquet/dataset/) --
    # data files skipped entirely by partition-value pruning against
    # the manifest (the scan never opens them — this composes BEFORE
    # the per-file stats/bloom/page-index layers above)
    dataset_files_pruned: int = 0
    # orphaned staging files / stale journals moved to _quarantine/ by
    # the dataset orphan sweep (never deleted silently)
    dataset_orphans_swept: int = 0
    # exact-filter selectivity accounting: rows that entered exact
    # predicate evaluation vs rows that survived it (selectivity =
    # filter_rows_out / filter_rows_in); rows pruned statically never
    # enter these — rows_pruned covers them
    filter_rows_in: int = 0
    filter_rows_out: int = 0
    # -- gather / output placement (shard/scan.py gather_column et al.) --
    # bytes of assembled column globals that LANDED on destination
    # shards during the gather's reshard step: per-destination-shard
    # received bytes summed over the target's devices (padding
    # included).  Replicated out-sharding pays global_bytes x n_devices;
    # a 1:1 consumer-aligned placement pays ~global_bytes — flat in
    # mesh size.  The r05 "is the gather volume irreducible?" question
    # is answered by this counter, not conjecture.
    gather_bytes_moved: int = 0
    # the share of gather_bytes_moved that is pure replication (every
    # copy of a global byte beyond the first): replicated out-sharding
    # contributes global_bytes x (n_devices - 1); an evenly-sharded
    # consumer placement contributes 0.  True consumer fan-out (a spec
    # that replicates over some mesh axis) shows up here too —
    # proportional to the fan-out actually requested.
    gather_bytes_replicated: int = 0
    # wall spent in the gather's reshard/collective step (the
    # device-side half of gather time; host-side densify/pad/stack
    # assembly is the rest of the caller's gather wall)
    gather_reshard_s: float = 0.0
    # -- footer-keyed plan cache (kernels/plancache.py) --
    # per-(rg, column) lookups during device planning: hits skip the
    # transport competition (sample windows, token scans), misses run
    # it and store the verdicts; evictions are LRU drops under the
    # TPQ_PLAN_CACHE_MB byte budget.  All zero when the cache is off.
    plan_cache_hits: int = 0
    plan_cache_misses: int = 0
    plan_cache_evictions: int = 0
    # -- remote byte-range sources (io/source.py, io/rangecache.py) --
    # range requests actually issued to a remote source by the chunk
    # fetch path (after coalescing; cache hits never issue one) and the
    # requests *saved* by merging: a prefetch of R chunk ranges that
    # collapses to M fetches adds M to remote_ranges_fetched and R - M
    # to ranges_coalesced.  remote_bytes is the exact payload total of
    # issued fetches (gap bytes included — that's the trade the
    # coalescer makes); remote_retry counts retry-ladder re-issues
    # against remote sources (the remote twin of io_retries)
    remote_ranges_fetched: int = 0
    ranges_coalesced: int = 0
    remote_bytes: int = 0
    remote_retry: int = 0
    # tiered range cache: per-tier lookups split exactly into hits +
    # misses (conservation: hits + misses == lookups), evictions are
    # LRU drops, budget rejections and poison/invalidation removals
    cache_hits_mem: int = 0
    cache_misses_mem: int = 0
    cache_evictions_mem: int = 0
    cache_hits_disk: int = 0
    cache_misses_disk: int = 0
    cache_evictions_disk: int = 0
    # where the device-path wall went, each added by one
    # obs.trace.stage where the work happens: host plan phase (page
    # walk, decompression, run-table scans; wall summed over the plan
    # pool's threads, so plan_s can exceed the e2e wall) and the CPU
    # seconds of those threads inside it (plan_cpu_s <= plan_s; the
    # rest is GIL, lock and I/O wait); the consumer's wait on the plan
    # futures (plan_wait_s); stager transfer (put(), blocking to
    # completion); dispatch, the enqueue of each column's page
    # programs; and drain, the block_until_ready on the unit's
    # buffers.  plan_wait/transfer/dispatch/drain run in turn on the
    # consumer thread, so their sum is at most the consumer's wall.
    plan_s: float = 0.0
    plan_cpu_s: float = 0.0
    plan_wait_s: float = 0.0
    transfer_s: float = 0.0
    dispatch_s: float = 0.0
    drain_s: float = 0.0
    wall_s: float = 0.0
    _t0: float = dataclasses.field(default=0.0, repr=False)
    # structured telemetry (tpuparquet/obs/): named log2-bucket
    # histograms, recorded whenever this collector is active; and the
    # per-page event log, attached only by collect_stats(events=True)
    # (None otherwise — the hot paths check `st.events is not None`
    # before any per-page event work)
    hists: dict = dataclasses.field(default_factory=dict, repr=False)
    events: object = dataclasses.field(default=None, repr=False)

    # counter fields merged across worker collectors (everything
    # cumulative; wall_s/_t0 belong to the owning scope alone)
    _MERGE_FIELDS = (
        "row_groups", "chunks", "pages", "pages_device_snappy",
        "pages_device_planes", "pages_device_delta_lanes",
        "pages_device_encoded", "pages_host_values", "values",
        "bytes_compressed", "bytes_uncompressed", "bytes_staged",
        "pieces_staged", "stacked_arrays", "chunks_fused", "pages_fused",
        "programs_dispatched", "dict_bytes_pages", "dict_bytes_fixed_pages",
        "bytes_read", "read_s",
        "native_fallbacks", "pages_crc_verified", "crc_mismatches",
        "faults_injected", "io_retries", "dispatch_retries",
        "pages_degraded", "units_degraded", "units_quarantined",
        "files_salvaged", "row_groups_recovered", "files_quarantined",
        "metadata_rejects",
        "deadline_exceeded", "hedges_issued", "hedges_won",
        "checkpoints_written",
        "pages_written", "pages_assembled_native",
        "write_encode_s", "write_compress_s", "write_assemble_s",
        "codec_split_blocks", "codec_split_frames",
        "row_groups_pruned", "pages_pruned", "rows_pruned",
        "bloom_hits", "filter_rows_in", "filter_rows_out",
        "dataset_files_pruned", "dataset_orphans_swept",
        "gather_bytes_moved", "gather_bytes_replicated",
        "gather_reshard_s",
        "plan_cache_hits", "plan_cache_misses", "plan_cache_evictions",
        "remote_ranges_fetched", "ranges_coalesced", "remote_bytes",
        "remote_retry",
        "cache_hits_mem", "cache_misses_mem", "cache_evictions_mem",
        "cache_hits_disk", "cache_misses_disk", "cache_evictions_disk",
        "plan_s", "plan_cpu_s", "plan_wait_s", "transfer_s",
        "dispatch_s", "drain_s",
    )

    def merge_from(self, other: "DecodeStats") -> None:
        """Fold a worker collector's counts into this one (called on
        the coordinating thread after the worker is joined).  Histogram
        folds are exact (integer bucket adds); the worker's event log,
        if any, appends to this collector's."""
        for f in self._MERGE_FIELDS:
            setattr(self, f, getattr(self, f) + getattr(other, f))
        for name, h in other.hists.items():
            self.hist(name).merge_from(h)
        if other.events is not None and self.events is not None:
            self.events.merge_from(other.events)

    def hist(self, name: str):
        """Get-or-create the named histogram (obs.Histogram)."""
        h = self.hists.get(name)
        if h is None:
            from .obs.histogram import Histogram

            h = self.hists[name] = Histogram()
        return h

    @property
    def values_per_sec(self) -> float:
        return self.values / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def compression_ratio(self) -> float:
        if self.bytes_compressed == 0:
            return 1.0
        return self.bytes_uncompressed / self.bytes_compressed

    def as_dict(self) -> dict:
        return {
            "row_groups": self.row_groups,
            "chunks": self.chunks,
            "pages": self.pages,
            "pages_device_snappy": self.pages_device_snappy,
            "pages_device_planes": self.pages_device_planes,
            "pages_device_delta_lanes": self.pages_device_delta_lanes,
            "pages_device_encoded": self.pages_device_encoded,
            "pages_host_values": self.pages_host_values,
            "values": self.values,
            "bytes_compressed": self.bytes_compressed,
            "bytes_uncompressed": self.bytes_uncompressed,
            "bytes_staged": self.bytes_staged,
            "pieces_staged": self.pieces_staged,
            "stacked_arrays": self.stacked_arrays,
            "chunks_fused": self.chunks_fused,
            "pages_fused": self.pages_fused,
            "programs_dispatched": self.programs_dispatched,
            "dict_bytes_pages": self.dict_bytes_pages,
            "dict_bytes_fixed_pages": self.dict_bytes_fixed_pages,
            "bytes_read": self.bytes_read,
            "read_s": round(self.read_s, 6),
            "native_fallbacks": self.native_fallbacks,
            "pages_crc_verified": self.pages_crc_verified,
            "crc_mismatches": self.crc_mismatches,
            "faults_injected": self.faults_injected,
            "io_retries": self.io_retries,
            "dispatch_retries": self.dispatch_retries,
            "pages_degraded": self.pages_degraded,
            "units_degraded": self.units_degraded,
            "units_quarantined": self.units_quarantined,
            "files_salvaged": self.files_salvaged,
            "row_groups_recovered": self.row_groups_recovered,
            "files_quarantined": self.files_quarantined,
            "metadata_rejects": self.metadata_rejects,
            "deadline_exceeded": self.deadline_exceeded,
            "hedges_issued": self.hedges_issued,
            "hedges_won": self.hedges_won,
            "checkpoints_written": self.checkpoints_written,
            "pages_written": self.pages_written,
            "pages_assembled_native": self.pages_assembled_native,
            "write_encode_s": round(self.write_encode_s, 6),
            "write_compress_s": round(self.write_compress_s, 6),
            "write_assemble_s": round(self.write_assemble_s, 6),
            "codec_split_blocks": self.codec_split_blocks,
            "codec_split_frames": self.codec_split_frames,
            "row_groups_pruned": self.row_groups_pruned,
            "pages_pruned": self.pages_pruned,
            "rows_pruned": self.rows_pruned,
            "bloom_hits": self.bloom_hits,
            "dataset_files_pruned": self.dataset_files_pruned,
            "dataset_orphans_swept": self.dataset_orphans_swept,
            "filter_rows_in": self.filter_rows_in,
            "filter_rows_out": self.filter_rows_out,
            "selectivity": round(
                self.filter_rows_out / self.filter_rows_in, 6)
            if self.filter_rows_in else None,
            "gather_bytes_moved": self.gather_bytes_moved,
            "gather_bytes_replicated": self.gather_bytes_replicated,
            "gather_reshard_s": round(self.gather_reshard_s, 6),
            "plan_cache_hits": self.plan_cache_hits,
            "plan_cache_misses": self.plan_cache_misses,
            "plan_cache_evictions": self.plan_cache_evictions,
            "remote_ranges_fetched": self.remote_ranges_fetched,
            "ranges_coalesced": self.ranges_coalesced,
            "remote_bytes": self.remote_bytes,
            "remote_retry": self.remote_retry,
            "cache_hits_mem": self.cache_hits_mem,
            "cache_misses_mem": self.cache_misses_mem,
            "cache_evictions_mem": self.cache_evictions_mem,
            "cache_hits_disk": self.cache_hits_disk,
            "cache_misses_disk": self.cache_misses_disk,
            "cache_evictions_disk": self.cache_evictions_disk,
            "plan_s": round(self.plan_s, 6),
            "plan_cpu_s": round(self.plan_cpu_s, 6),
            "plan_wait_s": round(self.plan_wait_s, 6),
            "transfer_s": round(self.transfer_s, 6),
            "dispatch_s": round(self.dispatch_s, 6),
            "drain_s": round(self.drain_s, 6),
            "wall_s": round(self.wall_s, 6),
            "values_per_sec": round(self.values_per_sec, 1),
            "compression_ratio": round(self.compression_ratio, 3),
        }

    def summary(self) -> str:
        d = self.as_dict()
        return (
            f"decoded {d['values']:,} values in {d['pages']} pages / "
            f"{d['chunks']} chunks / {d['row_groups']} row groups; "
            f"{d['bytes_compressed']:,}B -> {d['bytes_uncompressed']:,}B "
            f"(x{d['compression_ratio']}); "
            f"{d['wall_s']:.4f}s = {d['values_per_sec']:,.0f} values/s"
            + (f"; staged {d['bytes_staged']:,}B to device in "
               f"{d['pieces_staged']:,} pieces "
               f"({d['stacked_arrays']:,} page arrays stacked)"
               if d["bytes_staged"] else "")
            + (f"; plan {d['plan_s']:.3f}s (cpu {d['plan_cpu_s']:.3f}s)"
               f" / plan wait {d['plan_wait_s']:.3f}s / transfer "
               f"{d['transfer_s']:.3f}s / dispatch {d['dispatch_s']:.3f}s"
               f" ({d['programs_dispatched']:,} programs, "
               f"{d['chunks_fused']}/{d['chunks']} chunks fused, "
               f"{d['dict_bytes_fixed_pages']}/{d['dict_bytes_pages']} "
               f"byte-array dictionary pages fixed-length)"
               f" / drain {d['drain_s']:.3f}s"
               if d["transfer_s"] else "")
            + (f"; {d['native_fallbacks']} native fallbacks (stale .so?)"
               if d["native_fallbacks"] else "")
            + (f"; crc verified {d['pages_crc_verified']} pages"
               if d["pages_crc_verified"] else "")
            + (f"; FAULTS: {d['crc_mismatches']} crc mismatches, "
               f"{d['faults_injected']} injected, "
               f"{d['io_retries']} io retries, "
               f"{d['dispatch_retries']} dispatch retries, "
               f"{d['pages_degraded']}p/{d['units_degraded']}u degraded "
               f"to host, {d['units_quarantined']} quarantined"
               if (d["crc_mismatches"] or d["faults_injected"]
                   or d["io_retries"] or d["dispatch_retries"]
                   or d["pages_degraded"] or d["units_degraded"]
                   or d["units_quarantined"]) else "")
            + (f"; TIME: {d['deadline_exceeded']} deadlines exceeded, "
               f"{d['hedges_issued']} hedges issued "
               f"({d['hedges_won']} won), "
               f"{d['checkpoints_written']} checkpoints"
               if (d["deadline_exceeded"] or d["hedges_issued"]
                   or d["checkpoints_written"]) else "")
            + (f"; WRITE: {d['pages_written']} pages "
               f"({d['pages_assembled_native']} native), "
               f"encode {d['write_encode_s']:.3f}s / compress "
               f"{d['write_compress_s']:.3f}s / assemble "
               f"{d['write_assemble_s']:.3f}s"
               + (f", {d['codec_split_blocks']} split blocks"
                  if d["codec_split_blocks"] else "")
               if d["pages_written"] else "")
            + (f"; {d['codec_split_frames']} codec frames "
               f"decoded parallel" if d["codec_split_frames"] else "")
            + (f"; PRUNE: {d['row_groups_pruned']} row groups / "
               f"{d['pages_pruned']} pages / {d['rows_pruned']} rows "
               f"pruned, {d['bloom_hits']} bloom hits"
               + (f", selectivity {d['selectivity']:.4f} "
                  f"({d['filter_rows_out']:,}/{d['filter_rows_in']:,})"
                  if d["filter_rows_in"] else "")
               if (d["row_groups_pruned"] or d["pages_pruned"]
                   or d["rows_pruned"] or d["bloom_hits"]
                   or d["filter_rows_in"]) else "")
            + (f"; GATHER: {d['gather_bytes_moved']:,}B to consumers "
               f"({d['gather_bytes_replicated']:,}B replication), "
               f"reshard {d['gather_reshard_s']:.3f}s"
               if (d["gather_bytes_moved"] or d["gather_reshard_s"])
               else "")
            + (f"; PLAN CACHE: {d['plan_cache_hits']} hits / "
               f"{d['plan_cache_misses']} misses / "
               f"{d['plan_cache_evictions']} evictions"
               if (d["plan_cache_hits"] or d["plan_cache_misses"]
                   or d["plan_cache_evictions"]) else "")
            + (f"; REMOTE: {d['remote_ranges_fetched']} ranges "
               f"({d['ranges_coalesced']} coalesced away), "
               f"{d['remote_bytes']:,}B fetched, "
               f"{d['remote_retry']} retries; cache mem "
               f"{d['cache_hits_mem']}/{d['cache_misses_mem']}"
               f"/{d['cache_evictions_mem']} disk "
               f"{d['cache_hits_disk']}/{d['cache_misses_disk']}"
               f"/{d['cache_evictions_disk']} (hit/miss/evict)"
               if (d["remote_ranges_fetched"] or d["remote_retry"]
                   or d["cache_hits_mem"] or d["cache_misses_mem"]
                   or d["cache_hits_disk"] or d["cache_misses_disk"])
               else "")
            + (f"; SALVAGE: {d['files_salvaged']} files salvaged "
               f"({d['row_groups_recovered']} row groups recovered), "
               f"{d['files_quarantined']} files quarantined, "
               f"{d['metadata_rejects']} metadata rejects"
               if (d["files_salvaged"] or d["files_quarantined"]
                   or d["metadata_rejects"]) else "")
        )

    def histograms_dict(self) -> dict:
        """Sparse JSON form of every recorded histogram."""
        return {name: h.as_dict() for name, h in sorted(self.hists.items())}

    # -- exact wire form (cross-host aggregation) -----------------------

    def to_state(self) -> dict:
        """JSON-serializable EXACT state: unrounded counters + wall +
        histograms (``as_dict`` rounds for display; aggregation must
        not).  The event log does not ship — it is per-host detail."""
        d = {f: getattr(self, f) for f in self._MERGE_FIELDS}
        d["wall_s"] = self.wall_s
        if self.hists:
            d["hists"] = self.histograms_dict()
        return d

    @classmethod
    def from_state(cls, d: dict) -> "DecodeStats":
        from .obs.histogram import Histogram

        st = cls()
        for f in cls._MERGE_FIELDS:
            if f in d:
                setattr(st, f, d[f])
        st.wall_s = d.get("wall_s", 0.0)
        for name, h in (d.get("hists") or {}).items():
            st.hists[name] = Histogram.from_dict(h)
        return st


_tls = threading.local()


def current_stats() -> DecodeStats | None:
    """The active collector ON THIS THREAD, or None (the hot path
    checks this).  Thread-local: a worker thread planning or encoding
    on behalf of a scope uses :func:`worker_stats` and its coordinator
    merges — plain ``+=`` on a shared collector from racing threads
    loses increments, and ``values``/``bytes_*`` feed headline bench
    fields."""
    return getattr(_tls, "active", None)


@contextlib.contextmanager
def collect_stats(events: bool = False):
    """Collect decode counters for the enclosed scope (on THIS thread —
    see the module docstring for the worker-thread contract).

    ``events=True`` additionally attaches a per-page event log
    (``st.events``, an :class:`~tpuparquet.obs.events.EventLog`): one
    record per decoded page with the chosen transport and the gate's
    wire-size numbers, plus host-side phase spans for the Perfetto
    export.  Off by default — the event log allocates per page."""
    prev = getattr(_tls, "active", None)
    st = DecodeStats()
    if events:
        from .obs.events import EventLog

        st.events = EventLog()
    st._t0 = time.perf_counter()
    _tls.active = st
    try:
        yield st
    finally:
        st.wall_s = time.perf_counter() - st._t0
        _tls.active = prev
        # always-on regime bridge (obs/live.py): every collect_stats
        # scope folds into the process-wide metrics registry on exit,
        # exactly once per count — a nested scope SHADOWS the outer
        # (its counts never reach the outer collector), and worker
        # collectors merge into their coordinator instead of folding,
        # so no count lands twice.  One ~40-field pass per scope;
        # TPQ_LIVE_METRICS=0 disables.
        from .obs.live import fold_stats

        fold_stats(st)


@contextlib.contextmanager
def adopt_stats(st: "DecodeStats"):
    """Temporarily install an EXISTING collector as this thread's
    active one (no wall bookkeeping — the owner keeps its own clock).
    The scan drivers use this to meter unit decodes into a
    scan-lifetime collector when the caller has no collector of their
    own, so the always-on metrics registry sees scans nobody wrapped
    in ``collect_stats()``.  Same restore discipline as the scopes
    above; never nest around a scope you don't own."""
    prev = getattr(_tls, "active", None)
    _tls.active = st
    try:
        yield st
    finally:
        _tls.active = prev


@contextlib.contextmanager
def worker_stats(like: "DecodeStats | None" = None):
    """Fresh per-thread collector for a pool worker; yields it.  The
    coordinating thread merges the result into ITS active collector
    (``merge_from``) after joining the worker — no cross-thread
    increments, no lost counts.

    ``like`` is the coordinator's collector (or None): when it carries
    an event log, the worker gets its own log on the SAME clock
    (shared ``t0``), so merged span timestamps line up in one
    timeline."""
    prev = getattr(_tls, "active", None)
    st = DecodeStats()
    if like is not None and like.events is not None:
        from .obs.events import EventLog

        st.events = EventLog(t0=like.events.t0)
    _tls.active = st
    try:
        yield st
    finally:
        _tls.active = prev


# counters that carry fault-layer observability (injected faults, CRC
# rejects, retry attempts, deadline expiries, hedges): the only thing
# a FAILED worker attempt may contribute to its coordinator —
# everything else from a failed attempt would be a phantom count.
# These must cover every counter the fault EVENTS (which DO merge on
# failure) can record, or counters and events diverge.
_FAULT_OBSERVABILITY_FIELDS = ("faults_injected", "crc_mismatches",
                               "io_retries", "remote_retry",
                               "dispatch_retries",
                               "deadline_exceeded", "hedges_issued",
                               "hedges_won")


def merge_worker_stats(st: "DecodeStats | None",
                       ws: "DecodeStats | None", *,
                       failed: bool) -> None:
    """Fold a worker/attempt collector into the coordinator's with the
    resilient-attempt exactness policy: EVERYTHING on success;
    fault-layer observability only on failure (a unit that retried N
    times still counts its pages/values/bytes exactly once, and
    aborted attempts leave no phantom page events).  The single owner
    of this policy — used by the retry ladder
    (``kernels.device.read_row_group_device_resilient``) and the
    deadline/hedge worker threads (``tpuparquet/deadline.py``)."""
    if st is None or ws is None:
        return
    if not failed:
        st.merge_from(ws)
        return
    for f in _FAULT_OBSERVABILITY_FIELDS:
        setattr(st, f, getattr(st, f) + getattr(ws, f))
    if st.events is not None and ws.events is not None:
        st.events.faults.extend(ws.events.faults)


@contextlib.contextmanager
def trace(log_dir: str):
    """JAX profiler trace of the enclosed scope (view in TensorBoard /
    Perfetto).  Device-side kernel timings come from the profiler; the
    counters above stay host-side and cheap."""
    import jax

    with jax.profiler.trace(log_dir):
        yield
