"""Per-scan resource attribution + the span-graph critical-path walk.

Two halves, one consumer (``parquet-tool doctor`` and the admission
controller the serve layer will grow):

**Ledgers** — exact per-scan-label resource accounting.  The scan
drivers already fold their ambient collector's *delta* into the
process :class:`~tpuparquet.obs.live.MetricsRegistry` at every unit
boundary (``LiveFold``); this module gives each scan label a
:class:`ScanLedger` fed the *same* delta dict, so by construction

    sum over scan ledgers of counter X  ==  registry total of X

for every counter the scans produced — the conservation property any
per-tenant byte/deadline budget must meter against.  Ledgers expose
the derived views an operator wants (cpu-seconds by stage, bytes
read/staged/moved, pages decoded, peak arena occupancy) and merge
exactly across threads (per-unit folds are driver-thread-serial) and
hosts (``shard.distributed.allgather_ledgers``: counter-wise sums,
peak as max).

**Span analysis** — the critical-path walk over a trace
(:mod:`~tpuparquet.obs.trace`).  For every span, its *exclusive* time
is its duration minus the union of its children's intervals; summed
by stage over a trace they give the scan's stage totals.  A unit's
wall decomposes exactly: each instant goes to the deepest span of the
unit's subtree running then (buckets sum to the unit duration even
where column plans overlap; gaps land in ``driver``).  :func:`diagnose` folds that into the bound verdict
(read-bound / plan-bound / decompress-bound / decode-bound /
gather-bound), ranks straggler units against the rolling p95 of unit
walls (:class:`~tpuparquet.deadline.LatencyTracker` — the same
detector the live progress view uses), and flags plan-pool
oversubscription (total plan seconds ≫ plan wall window × usable
cores — the PLAN_SCALE_r06 thread-degradation signature).
"""

from __future__ import annotations

import threading

__all__ = [
    "ScanLedger", "ledger", "ledgers_snapshot", "reset_ledgers",
    "merge_ledger_states", "stage_seconds", "stage_verdict",
    "remote_report",
    "STAGE_OF", "VERDICT_OF",
    "span_tree", "exclusive_times", "unit_reports", "diagnose",
    "format_diagnosis",
]

#: span name -> canonical stage bucket
STAGE_OF = {
    "read": "read", "read_replica": "read", "retry": "read",
    "plan": "plan", "plan_wait": "plan_wait",
    "decompress": "decompress",
    "transfer": "transfer", "stage": "transfer",
    "dispatch": "dispatch", "drain": "drain",
    "gather": "gather",
    "page_write": "write", "encode": "write", "compress": "write",
}

#: stage bucket -> doctor verdict (transfer, dispatch and drain are the
#: decode side of the wall: bytes moving to, kernels enqueued on, and
#: kernels finishing on the device; a consumer waiting on its plans is
#: held by planning)
VERDICT_OF = {
    "read": "read-bound", "plan": "plan-bound",
    "plan_wait": "plan-bound",
    "decompress": "decompress-bound", "transfer": "decode-bound",
    "dispatch": "decode-bound", "drain": "decode-bound",
    "gather": "gather-bound",
}

#: stage buckets of a consumer WAITING on work other threads do: the
#: wait overlaps that work (the unit's plan spans on the pool), so in
#: a unit's decomposition a wait keeps only the time no sibling span
#: covers
_WAITS = frozenset({"plan_wait"})

#: DecodeStats counter -> stage, for the ledger/profile cpu_s view
#: (decompress rides inside plan_s on the live pipeline — the plan
#: phase decompresses page bodies; it stays a separate bucket only
#: where a trace carries explicit decompress spans).  plan_wait_s is
#: not here: it is the consumer's wait on plan_s, not work of its own.
_STAGE_COUNTERS = {
    "read": "read_s", "plan": "plan_s", "transfer": "transfer_s",
    "dispatch": "dispatch_s", "drain": "drain_s",
    "gather": "gather_reshard_s",
}


def stage_seconds(counters: dict) -> dict:
    """Per-stage cpu-seconds view over a counter dict (a ledger's, a
    ``DecodeStats.as_dict()``, or a registry snapshot) — the shared
    derivation ``parquet-tool profile``/``top``/``doctor`` all print,
    so the surfaces agree on numbers by construction.

    The buckets are DISJOINT: ``read_s`` accrues inside the plan
    timing window (``chunk_blob`` is called by the plan phase), so the
    ``plan`` bucket here is ``plan_s - read_s`` (clamped at zero for
    the CPU read paths that fetch chunks outside any plan timer) —
    exactly the subtraction the trace-based doctor performs when it
    takes the plan span's exclusive time over its child read span."""
    out = {stage: round(float(counters.get(c, 0) or 0), 6)
           for stage, c in _STAGE_COUNTERS.items()}
    out["plan"] = round(max(out["plan"] - out["read"], 0.0), 6)
    return out


def remote_report(counters: dict,
                  verdict: str | None = None) -> dict | None:
    """The doctor's REMOTE section over one counter dict (a ledger's
    ``counters``, a ``DecodeStats.as_dict()``, or a registry
    snapshot), or None when the scan never touched a remote source or
    a range cache.

    ``hit_ratio`` is cache hits (mem + disk) over total range demand
    (hits + origin fetches) — the fraction of range reads the cache
    absorbed.  ``origin_bound`` fires only when the trace already says
    ``read-bound`` (pass :func:`diagnose`'s ``verdict``) AND the
    origin absorbed at least half the demand: a read-bound scan whose
    cache is doing its job is disk-bound, not origin-bound, and the
    cures differ (more spindles vs deeper prefetch / bigger cache)."""
    fetched = int(counters.get("remote_ranges_fetched", 0) or 0)
    hits = (int(counters.get("cache_hits_mem", 0) or 0)
            + int(counters.get("cache_hits_disk", 0) or 0))
    misses = (int(counters.get("cache_misses_mem", 0) or 0)
              + int(counters.get("cache_misses_disk", 0) or 0))
    retries = int(counters.get("remote_retry", 0) or 0)
    if not (fetched or hits or misses or retries):
        return None
    demand = hits + fetched
    ratio = hits / demand if demand > 0 else 0.0
    return {
        "origin_fetches": fetched,
        "origin_bytes": int(counters.get("remote_bytes", 0) or 0),
        "ranges_coalesced": int(
            counters.get("ranges_coalesced", 0) or 0),
        "cache_hits_mem": int(counters.get("cache_hits_mem", 0) or 0),
        "cache_hits_disk": int(
            counters.get("cache_hits_disk", 0) or 0),
        "cache_misses_disk": int(
            counters.get("cache_misses_disk", 0) or 0),
        "cache_evictions_disk": int(
            counters.get("cache_evictions_disk", 0) or 0),
        "hit_ratio": round(ratio, 4),
        "retries": retries,
        "hedges_issued": int(counters.get("hedges_issued", 0) or 0),
        "hedges_won": int(counters.get("hedges_won", 0) or 0),
        "origin_bound": bool(verdict == "read-bound"
                             and fetched > 0 and ratio < 0.5),
    }


def stage_verdict(counters: dict) -> str | None:
    """Counter-only doctor verdict: the :data:`VERDICT_OF` name of
    the dominant :func:`stage_seconds` bucket, or None when nothing
    has accrued.  The trace-based :func:`diagnose` is strictly richer
    (exclusive times, tails, oversubscription); this is the cheap
    always-available form the serve arbiter's adaptive loop feeds on
    — same buckets, same vocabulary, so ``parquet-tool doctor`` and
    the rebalancer never disagree about what a tenant is bound by."""
    stages = stage_seconds(counters)
    stage = max(stages, key=lambda s: stages[s])
    if stages[stage] <= 0:
        return None
    return VERDICT_OF.get(stage)


class ScanLedger:
    """Exact resource ledger for one scan label.

    ``fold_delta`` accumulates counter deltas (the same dicts
    ``LiveFold`` applies to the registry — counters are EXACT);
    ``note_peak`` keeps the max of observed arena-occupancy high-water
    marks, which is process-shared telemetry, not an exact per-scan
    number: arenas are one pool, so a scan's ``peak_arena_bytes`` is
    the highest shared-pool occupancy seen during its unit windows —
    an upper bound that includes concurrent scans' borrows (see
    :func:`tpuparquet.kernels.arena.take_arena_peak`).  Thread model:
    folds happen on the scan's driving thread at unit boundaries; the
    snapshot readers copy under the GIL (same discipline as the
    registry shards)."""

    __slots__ = ("label", "counters", "peak_arena_bytes", "scans")

    def __init__(self, label: str):
        self.label = label
        self.counters: dict = {}
        self.peak_arena_bytes = 0
        self.scans = 0

    def fold_delta(self, delta: dict) -> None:
        c = self.counters
        for k, v in delta.items():
            c[k] = c.get(k, 0) + v

    def note_peak(self, peak_bytes: int) -> None:
        if peak_bytes > self.peak_arena_bytes:
            self.peak_arena_bytes = peak_bytes

    def as_dict(self) -> dict:
        c = dict(self.counters)
        return {
            "label": self.label,
            "scans": self.scans,
            "cpu_s": stage_seconds(c),
            "bytes": {
                "read": c.get("bytes_read", 0),
                "staged": c.get("bytes_staged", 0),
                "moved": c.get("gather_bytes_moved", 0),
            },
            "pages": c.get("pages", 0),
            "rows": c.get("values", 0),
            "peak_arena_bytes": self.peak_arena_bytes,
            "counters": c,
        }

    # -- exact wire form (cross-host merge) --------------------------------

    def to_state(self) -> dict:
        return {"label": self.label, "scans": self.scans,
                "counters": dict(self.counters),
                "peak_arena_bytes": self.peak_arena_bytes}

    @classmethod
    def from_state(cls, d: dict) -> "ScanLedger":
        led = cls(d["label"])
        led.scans = int(d.get("scans", 0))
        led.counters = dict(d.get("counters") or {})
        led.peak_arena_bytes = int(d.get("peak_arena_bytes", 0))
        return led

    def merge_from(self, other: "ScanLedger") -> None:
        """Exact fold: counters sum, peak is the max (occupancy peaks
        on different hosts are concurrent, not additive), scan count
        sums."""
        self.fold_delta(other.counters)
        self.note_peak(other.peak_arena_bytes)
        self.scans += other.scans


_lock = threading.Lock()
_ledgers: dict[str, ScanLedger] = {}


def ledger(label: str) -> ScanLedger:
    """Get-or-create the process ledger for a scan label (two scans
    sharing a label share a ledger — per-tenant accounting keys on
    the label, exactly like the progress gauges)."""
    with _lock:
        led = _ledgers.get(label)
        if led is None:
            led = _ledgers[label] = ScanLedger(label)
        return led


def ledgers_snapshot() -> dict:
    """``{label: ScanLedger.as_dict()}`` for every scan label this
    process has run."""
    with _lock:
        items = list(_ledgers.items())
    return {label: led.as_dict() for label, led in sorted(items)}


def ledgers_state() -> dict:
    """Exact wire form of every ledger (cross-host merge)."""
    with _lock:
        items = list(_ledgers.items())
    return {label: led.to_state() for label, led in items}


def reset_ledgers() -> None:
    with _lock:
        _ledgers.clear()


def merge_ledger_states(states: list[dict]) -> dict:
    """Fold per-host ``ledgers_state()`` dicts into one exact
    fleet-wide ``{label: ScanLedger}`` (counters sum label-wise — the
    single-host ledger of the union corpus)."""
    out: dict[str, ScanLedger] = {}
    for state in states:
        for label, d in state.items():
            led = ScanLedger.from_state(d)
            if label in out:
                out[label].merge_from(led)
            else:
                out[label] = led
    return out


# ----------------------------------------------------------------------
# Span analysis (the doctor's walk)
# ----------------------------------------------------------------------

def span_tree(spans: list[dict]) -> tuple[dict, dict, list[dict]]:
    """Index a span list: ``(by_id, children, roots)``.  Spans whose
    parent is absent from the list (a trimmed ring) are treated as
    roots of their own subtree rather than dropped — the walk then
    reports what it can see."""
    by_id = {s["span"]: s for s in spans}
    children: dict = {}
    roots = []
    for s in spans:
        p = s.get("parent")
        if p is not None and p in by_id:
            children.setdefault(p, []).append(s)
        else:
            roots.append(s)
    return by_id, children, roots


def _union_len(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_a, cur_b = intervals[0]
    for a, b in intervals[1:]:
        if a > cur_b:
            total += cur_b - cur_a
            cur_a, cur_b = a, b
        elif b > cur_b:
            cur_b = b
    return total + (cur_b - cur_a)


def exclusive_times(spans: list[dict]) -> dict:
    """``{span_id: exclusive_seconds}``: each span's duration minus
    the union of its children's intervals clipped to it.  Summing a
    subtree's exclusive times reproduces the subtree root's duration
    exactly (plus nothing, minus nothing) — the invariant the stage
    decomposition rests on."""
    _, children, _ = span_tree(spans)
    out = {}
    for s in spans:
        t0, t1 = s["t0"], s["t0"] + s.get("dur", 0.0)
        kids = []
        for c in children.get(s["span"], ()):
            a = max(c["t0"], t0)
            b = min(c["t0"] + c.get("dur", 0.0), t1)
            if b > a:
                kids.append((a, b))
        out[s["span"]] = max(s.get("dur", 0.0) - _union_len(kids), 0.0)
    return out


def _subtree_stages(root: dict, children: dict) -> dict:
    """Wall-by-stage over one span's subtree: every instant of the
    root's interval goes to one bucket — that of the deepest span of
    the subtree running then, or ``driver`` (loop bookkeeping, window
    gaps) where none runs — so the buckets always sum to the root's
    duration, even where spans overlap (column plans on a pool).  A
    wait (:data:`_WAITS`) ranks below every other span: a consumer
    waiting on its unit's plan tasks counts only while none of them
    runs."""
    lo, hi = root["t0"], root["t0"] + root.get("dur", 0.0)
    spans = []   # (start, end, rank, bucket), clipped to the root
    stack = [(c, 1) for c in children.get(root["span"], ())]
    while stack:
        s, depth = stack.pop()
        if s.get("status") == "cancelled":
            # abandoned work (hedge losers, dropped pipeline units):
            # real seconds, but duplicate/discarded — kept out of the
            # stage buckets so it cannot tilt a bound verdict
            bucket = "cancelled"
        else:
            bucket = STAGE_OF.get(s.get("name"), "other")
        a, b = max(s["t0"], lo), min(s["t0"] + s.get("dur", 0.0), hi)
        if b > a:
            spans.append((a, b, 0 if bucket in _WAITS else depth,
                          bucket))
        stack.extend((c, depth + 1) for c in children.get(s["span"], ()))
    edges = sorted({lo, hi, *(a for a, *_ in spans),
                    *(b for _, b, *_ in spans)})
    stages: dict = {}
    for x, y in zip(edges, edges[1:]):
        rank, bucket = -1, "driver"
        for a, b, r, bk in spans:
            if a <= x and y <= b and r > rank:
                rank, bucket = r, bk
        stages[bucket] = stages.get(bucket, 0.0) + (y - x)
    return stages


def _top_child(root: dict, children: dict) -> dict | None:
    """The longest direct child span (the straggler's offender)."""
    kids = children.get(root["span"])
    if not kids:
        return None
    return max(kids, key=lambda c: c.get("dur", 0.0))


def _coords(s: dict) -> dict:
    return {k: s[k] for k in ("unit", "file", "row_group", "column",
                              "page", "replica") if k in s}


def unit_reports(spans: list[dict]) -> list[dict]:
    """Per-unit decomposition: one row per ``name == "unit"`` span
    with its wall, its stage buckets (summing to the wall), the stage
    that bounds it, and the coordinates of its largest child."""
    _, children, _ = span_tree(spans)
    rows = []
    for s in spans:
        if s.get("name") != "unit":
            continue
        stages = _subtree_stages(s, children)
        timed = {k: v for k, v in stages.items() if k in VERDICT_OF}
        bound = max(timed, key=timed.get) if timed else "driver"
        top = _top_child(s, children)
        rows.append({
            "unit": s.get("unit"),
            "coords": _coords(s),
            "status": s.get("status", "ok"),
            "dur_s": round(s.get("dur", 0.0), 6),
            "stages_s": {k: round(v, 6)
                         for k, v in sorted(stages.items())},
            "bound": bound,
            "top_child": None if top is None else {
                "name": top.get("name"), "dur_s":
                round(top.get("dur", 0.0), 6), **_coords(top)},
        })
    rows.sort(key=lambda r: (r["unit"] is None, r["unit"]))
    return rows


def diagnose(spans: list[dict], p95s: dict | None = None) -> dict:
    """The doctor's whole-trace verdict.

    Walks one trace's spans (filter by trace id first when a snapshot
    holds several): per-unit stage decomposition, scan-level stage
    totals and shares, the bound verdict, stragglers ranked against
    the rolling p95 of unit walls (``p95s`` optionally pins
    externally tracked per-stage p95s — e.g. from a live
    ``deadline.LatencyTracker`` — into the report), and the plan-pool
    concurrency note that turns the PLAN_SCALE thread-degradation
    mystery into one line."""
    from ..deadline import LatencyTracker

    by_id, children, roots = span_tree(spans)
    excl = exclusive_times(spans)
    units = unit_reports(spans)
    scan_roots = [r for r in roots if r.get("name") == "scan"]
    root = scan_roots[0] if scan_roots else (roots[0] if roots else None)
    # wall = the whole trace's envelope, not just the root span's
    # duration: post-scan gathers (emitted under the retained root
    # context after the root closed) must count toward a gather-bound
    # verdict
    wall = (max(s["t0"] + s.get("dur", 0.0) for s in spans)
            - min(s["t0"] for s in spans)) if spans else 0.0

    # scan-level stage totals: exclusive time by stage over everything
    # (cancelled spans — hedge losers, dropped units — bucket apart so
    # abandoned duplicate work cannot tilt the verdict)
    stages: dict = {}
    for s in spans:
        if s.get("status") == "cancelled":
            bucket = "cancelled"
        elif s.get("name") in ("scan", "unit"):
            bucket = "driver"
        else:
            bucket = STAGE_OF.get(s.get("name"), "other")
        stages[bucket] = stages.get(bucket, 0.0) + excl.get(s["span"],
                                                            0.0)
    timed = {k: v for k, v in stages.items() if k in VERDICT_OF}
    timed_total = sum(timed.values())
    if timed:
        bound_stage = max(timed, key=timed.get)
        verdict = VERDICT_OF[bound_stage]
        # share of the TIMED work, not of wall: stage seconds sum
        # across pool/hedge threads, so a wall-relative ratio would
        # read >100% whenever stages ran in parallel (and could crown
        # the widest-parallel stage rather than the binding one)
        share = timed[bound_stage] / timed_total if timed_total > 0 \
            else 0.0
    else:
        bound_stage, verdict, share = None, "no-spans", 0.0

    # stragglers: each unit's wall vs the LatencyTracker p95 of its
    # SIBLINGS (leave-one-out — in a small scan one huge unit IS the
    # p95, and ranking it against itself would hide it; the live
    # progress view has the same detector in rolling form).  Only
    # units already past 1.5x the global median are candidates, so
    # the LOO pass stays linear in practice.
    tracker = LatencyTracker(window=256, min_samples=4)
    for u in units:
        tracker.record(u["dur_s"])
    p95 = tracker.quantile(0.95)
    stragglers = []
    if len(units) >= 4:
        durs = sorted(u["dur_s"] for u in units)
        median = durs[len(durs) // 2]
        for u in units:
            if u["dur_s"] <= max(median * 1.5, 0.001):
                continue
            rest = list(durs)
            rest.remove(u["dur_s"])
            loo = LatencyTracker(window=256, min_samples=3)
            for d in rest[-256:]:
                loo.record(d)
            p95_loo = loo.quantile(0.95)
            if p95_loo is not None and \
                    u["dur_s"] > max(p95_loo * 1.5, 0.001):
                stragglers.append(u)
        stragglers.sort(key=lambda u: -u["dur_s"])

    # plan-pool concurrency: total plan-span seconds vs the time plan
    # work was ACTIVE (the union of the plan intervals, not the whole
    # scan window — pipelined plans run in bursts between transfers).
    # On an N-core box an active overlap well above N means the pool
    # is oversubscribed: plan tasks timeslice against each other, each
    # task's wall inflates, and pipelined plan_s degrades with thread
    # count — exactly the PLAN_SCALE_r06 signature
    plan_spans = [s for s in spans
                  if STAGE_OF.get(s.get("name")) == "plan"]
    plan_note = None
    if plan_spans:
        total = sum(s.get("dur", 0.0) for s in plan_spans)
        busy = max(_union_len(
            [(s["t0"], s["t0"] + s.get("dur", 0.0))
             for s in plan_spans]), 1e-9)
        tids = len({s.get("tid") for s in plan_spans})
        usable = root.get("usable_cpus") if root is not None else None
        concurrency = total / busy
        plan_note = {
            "plan_total_s": round(total, 6),
            "plan_busy_s": round(busy, 6),
            "concurrency": round(concurrency, 3),
            "threads": tids,
            "usable_cpus": usable,
            "oversubscribed": bool(
                usable is not None and tids > usable
                and concurrency > usable * 1.25),
        }

    return {
        "trace": root.get("trace") if root is not None else None,
        "label": root.get("label") if root is not None else None,
        "wall_s": round(wall, 6),
        "units": len(units),
        "unit_rows": units,
        "stages_s": {k: round(v, 6) for k, v in sorted(stages.items())},
        "stage_share": {k: round(v / timed_total, 4)
                        if timed_total > 0 else 0.0
                        for k, v in sorted(timed.items())},
        # timed work over wall: ~1.0 means the spans account for the
        # whole wall; >1.0 means stages genuinely ran in parallel
        # (average timed parallelism), <1.0 means untimed driver gaps
        "coverage": round(timed_total / wall, 4) if wall > 0 else 0.0,
        "bound_stage": bound_stage,
        "verdict": verdict,
        "verdict_share": round(share, 4),
        "p95_unit_s": None if p95 is None else round(p95, 6),
        "stragglers": stragglers[:8],
        "plan_pool": plan_note,
        "external_p95s": p95s or None,
    }


def format_diagnosis(d: dict, ledgers: dict | None = None) -> str:
    """Human rendering of one :func:`diagnose` report (the
    ``parquet-tool doctor`` screen)."""
    lines = []
    lines.append(
        f"trace {d.get('trace') or '?'}"
        + (f"  label={d['label']}" if d.get("label") else "")
        + f"  units={d['units']}  wall={d['wall_s']:.3f}s")
    if d.get("stages_s"):
        parts = []
        for k, v in sorted(d["stages_s"].items(),
                           key=lambda kv: -kv[1]):
            if v <= 0:
                continue
            shr = f" ({100 * v / d['wall_s']:.1f}%)" \
                if d["wall_s"] > 0 else ""
            parts.append(f"{k} {v:.3f}s{shr}")
        lines.append("  stages: " + "  ".join(parts))
    lines.append(
        f"  verdict: {d['verdict']}"
        + (f" — {d['bound_stage']} is "
           f"{100 * d['verdict_share']:.1f}% of the timed work"
           if d.get("bound_stage") else "")
        + f"  (timed work covers {100 * d.get('coverage', 0):.1f}%"
          " of wall)")
    pp = d.get("plan_pool")
    if pp:
        note = (f"  plan pool: {pp['plan_total_s']:.3f}s of plan over "
                f"{pp['plan_busy_s']:.3f}s of active plan time on "
                f"{pp['threads']} thread(s)"
                + (f", {pp['usable_cpus']} usable core(s)"
                   if pp.get("usable_cpus") is not None else "")
                + f" — concurrency {pp['concurrency']:.2f}")
        if pp.get("oversubscribed"):
            note += ("  OVERSUBSCRIBED: plan tasks timeslice against "
                     "each other; try TPQ_PLAN_THREADS="
                     + str(pp["usable_cpus"]))
        lines.append(note)
    if d.get("p95_unit_s") is not None:
        lines.append(f"  unit p95: {d['p95_unit_s']:.3f}s")
    for u in d.get("stragglers") or []:
        top = u.get("top_child")
        lines.append(
            f"  STRAGGLER unit {u['unit']} "
            f"({', '.join(f'{k}={v}' for k, v in u['coords'].items() if k != 'unit')}): "
            f"{u['dur_s']:.3f}s, bound by {u['bound']}"
            + (f" — top span {top['name']} {top['dur_s']:.3f}s "
               + " ".join(f"{k}={v}" for k, v in top.items()
                          if k not in ("name", "dur_s"))
               if top else ""))
    if d.get("unit_rows"):
        tally: dict = {}
        for u in d["unit_rows"]:
            tally[u["bound"]] = tally.get(u["bound"], 0) + 1
        lines.append("  per-unit bound: " + "  ".join(
            f"{k}:{v}" for k, v in sorted(tally.items(),
                                          key=lambda kv: -kv[1])))
    for label, led in sorted((ledgers or {}).items()):
        cpu = led.get("cpu_s", {})
        by = led.get("bytes", {})
        lines.append(
            f"  ledger[{label}]: cpu "
            + " ".join(f"{k}={v:.3f}s" for k, v in sorted(cpu.items())
                       if v)
            + f"  bytes read={by.get('read', 0):,} "
            f"staged={by.get('staged', 0):,} "
            f"moved={by.get('moved', 0):,}"
            + f"  pages={led.get('pages', 0)}"
            + (f"  peak_arena={led.get('peak_arena_bytes', 0):,}B"
               if led.get("peak_arena_bytes") else ""))
        rr = remote_report(led.get("counters") or {},
                           verdict=d.get("verdict"))
        if rr:
            lines.append(
                f"  REMOTE[{label}]: origin {rr['origin_fetches']} "
                f"fetches / {rr['origin_bytes']:,}B "
                f"(coalesced {rr['ranges_coalesced']})  cache hits "
                f"mem={rr['cache_hits_mem']} "
                f"disk={rr['cache_hits_disk']}  hit ratio "
                f"{100 * rr['hit_ratio']:.1f}%  retries={rr['retries']}"
                f"  hedges={rr['hedges_won']}/{rr['hedges_issued']}"
                + (f"  evictions={rr['cache_evictions_disk']}"
                   if rr["cache_evictions_disk"] else ""))
            if rr["origin_bound"]:
                lines.append(
                    "    ORIGIN-BOUND: read-bound and the origin "
                    f"absorbed {100 * (1 - rr['hit_ratio']):.1f}% of "
                    "range demand — deepen prefetch "
                    "(TPQ_PREFETCH_DEPTH) or grow the shared disk "
                    "cache (TPQ_CACHE_DISK_MB)")
    return "\n".join(lines)
