"""Time the dictionary BYTE_ARRAY gather's forms on the chip, and each
TPC-H lineitem column's chunk program for one row group.

    python tools/bench_dict_bytes.py kernels [--pages 50] [--reps 5]
    python tools/bench_dict_bytes.py columns [--root DIR] [--scale 0.35]

``kernels``: every candidate form at the four lineitem page shapes
(byte caps of 32 Ki, 128 Ki, 256 Ki and 1 Mi, 20,000 values a page, the
dictionaries of ``l_returnflag``, ``l_shipmode``, ``l_shipinstruct`` and
``l_comment``), over ``--pages`` pages in one program; each form's
valid bytes are checked against the per-byte ``searchsorted`` form
before it is timed.  ``columns``: the first 1,048,576-row group of the
benchmark's lineitem generator, each column read alone (from the
``tpuparquet`` under ``--root``, so that two versions can be compared
on one chip), the time from its chunk program's dispatch to
``block_until_ready``.  Each prints a line per measurement, then all
of them as one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VALUES = 20_000   # a page's values
ICNT = 32_768     # the bucket its index stream expands to
BLOCK = 1024


def _shapes(rng):
    """(name, dictionary entries) of the four lineitem page shapes."""
    comments = [bytes(rng.integers(97, 123, k, dtype=np.uint8))
                for k in rng.integers(10, 44, 38_000)]
    return [
        ("flag-32Ki", [b"A", b"N", b"R"]),
        ("shipmode-128Ki", [b"REG AIR", b"AIR", b"RAIL", b"SHIP",
                            b"TRUCK", b"MAIL", b"FOB"]),
        ("shipinstruct-256Ki", [b"DELIVER IN PERSON", b"COLLECT COD",
                                b"NONE", b"TAKE BACK RETURN"]),
        ("comment-1Mi", comments),
    ]


def _forms():
    import jax
    import jax.numpy as jnp

    def running(x, op):
        """Blocked inclusive scan along the last axis (``op`` is
        ``cumsum`` or ``cummax``), a block's scan plus its carry."""
        n = x.shape[-1]
        block = min(BLOCK, n)
        rows = x.reshape(x.shape[:-1] + (n // block, block))
        inner = op(rows, axis=-1)
        ends = inner[..., -1]
        if op is jnp.cumsum:
            carry = jnp.cumsum(ends, axis=-1) - ends
        else:
            carry = jnp.concatenate(
                [jnp.zeros_like(ends[..., :1]),
                 jax.lax.cummax(ends, axis=ends.ndim - 1)[..., :-1]],
                axis=-1)
            return jnp.maximum(inner, carry[..., None]).reshape(x.shape)
        return (inner + carry[..., None]).reshape(x.shape)

    def cummax(x, axis):
        return jax.lax.cummax(x, axis=axis % x.ndim)

    def prep(do, idx, nn):
        n_dict = do.shape[0] - 1
        idx = jnp.clip(idx, 0, n_dict - 1)
        lens = do[1:] - do[:-1]
        valid = jnp.arange(idx.shape[-1], dtype=jnp.int32) < nn
        return idx, valid, jnp.where(valid, lens[idx], 0)

    def searchsorted(method):
        def page(do, dd, idx, nn, cap):
            idx, _, contrib = prep(do, idx, nn)
            oo = jnp.concatenate([jnp.zeros((1,), do.dtype),
                                  jnp.cumsum(contrib).astype(do.dtype)])
            b = jnp.arange(cap, dtype=jnp.int32)
            val = jnp.searchsorted(oo[1:], b, side="right",
                                   method=method).astype(jnp.int32)
            val = jnp.minimum(val, idx.shape[0] - 1)
            src = do[idx[val]] + (b - oo[val])
            return dd[jnp.clip(src, 0, dd.shape[0] - 1)]
        return page

    def starts_of(contrib):
        ends = running(contrib, jnp.cumsum)
        return ends - contrib

    def var_max(do, dd, idx, nn, cap):
        """Each valid value's index at its start byte, filled by a
        blocked running max; then its source offset."""
        idx, valid, contrib = prep(do, idx, nn)
        starts = starts_of(contrib)
        pos = jnp.where(valid, starts, cap)
        marks = jnp.zeros((cap,), jnp.int32).at[pos].max(
            jnp.arange(idx.shape[0], dtype=jnp.int32), mode="drop")
        val = running(marks, cummax)
        delta = do[idx] - starts
        b = jnp.arange(cap, dtype=jnp.int32)
        return dd[jnp.clip(b + delta[val], 0, dd.shape[0] - 1)]

    def var_add(do, dd, idx, nn, cap):
        """Each valid value's change of source offset at its start
        byte, summed by a blocked running count: byte ``b`` reads
        ``b + (dict start - output start)`` of its value."""
        idx, valid, contrib = prep(do, idx, nn)
        starts = starts_of(contrib)
        delta = do[idx] - starts
        step = delta - jnp.concatenate([jnp.zeros_like(delta[..., :1]),
                                        delta[..., :-1]], axis=-1)
        pos = jnp.where(valid, starts, cap)
        if idx.ndim == 2:  # every page at once: one flat scatter
            g = idx.shape[0]
            pos = jnp.where(valid, pos + cap * jnp.arange(g)[:, None],
                            g * cap)
            marks = jnp.zeros((g * cap,), jnp.int32).at[
                pos.reshape(-1)].add(step.reshape(-1), mode="drop")
            marks = marks.reshape(g, cap)
        else:
            marks = jnp.zeros((cap,), jnp.int32).at[pos].add(
                step, mode="drop")
        b = jnp.arange(cap, dtype=jnp.int32)
        return dd[jnp.clip(b + running(marks, jnp.cumsum), 0,
                           dd.shape[0] - 1)]

    def fit(x, cap):
        n = x.shape[-1]
        if n >= cap:
            return x[..., :cap]
        pad = [(0, 0)] * (x.ndim - 1) + [(0, cap - n)]
        return jnp.pad(x, pad)

    def fixed_bytes(L):
        def page(do, dd, idx, nn, cap):
            idx = jnp.clip(idx, 0, do.shape[0] - 2)
            b = jnp.arange(cap, dtype=jnp.int32)
            v = jnp.minimum(b // L, idx.shape[0] - 1)
            src = do[idx[v]] + b % L
            return dd[jnp.clip(src, 0, dd.shape[0] - 1)]
        return page

    def fixed_rows(L):
        def page(do, dd, idx, nn, cap):
            idx = jnp.clip(idx, 0, do.shape[0] - 2)
            whole = dd.shape[0] // L * L
            rows = jnp.take(dd[:whole].reshape(-1, L), idx, axis=0,
                            mode="clip")
            return fit(rows.reshape(idx.shape[:-1] + (-1,)), cap)
        return page

    def wide(fn):
        """``fn`` gathering from the dictionary's bytes widened to
        int32, narrowed back after."""
        def page(do, dd, *a):
            return fn(do, dd.astype(jnp.int32), *a).astype(jnp.uint8)
        return page

    return {"searchsorted_scan": (searchsorted("scan"), "map"),
            "searchsorted_sort": (searchsorted("sort"), "map"),
            "searchsorted_compare_all": (searchsorted("compare_all"),
                                         "map"),
            "var_max": (var_max, "map"),
            "var_add": (var_add, "map"),
            "var_add_batched": (var_add, "batch"),
            "fixed_bytes": (fixed_bytes, "map"),
            "fixed_rows": (fixed_rows, "map"),
            "var_add_batched_i32": (wide(var_add), "batch"),
            "fixed_rows_batched": (fixed_rows, "batch"),
            "fixed_rows_batched_i32": (lambda L: wide(fixed_rows(L)),
                                       "batch")}


def kernels(args) -> dict:
    import jax

    rng = np.random.default_rng(27)
    out = {"device": jax.devices()[0].device_kind, "pages": args.pages,
           "values": VALUES, "rows": []}
    forms = _forms()
    for name, entries in _shapes(rng):
        lens = np.array([len(e) for e in entries], np.int32)
        do = np.zeros(len(entries) + 1, np.int32)
        np.cumsum(lens, out=do[1:])
        dd = np.frombuffer(b"".join(entries), np.uint8)
        idx = rng.integers(0, len(entries), (args.pages, ICNT),
                           dtype=np.int32)
        total = int(lens[idx[:, :VALUES]].sum(axis=1).max())
        cap = 32
        while cap < total:
            cap <<= 1
        nn = np.full(args.pages, VALUES, np.int32)
        fixed = int(lens[0]) if (lens == lens[0]).all() else 0
        dev = [jax.device_put(a) for a in (do, dd, idx, nn)]
        want = None
        for form, (fn, how) in forms.items():
            if form.startswith("fixed"):
                if not fixed:
                    continue
                fn = fn(fixed)
            if form.endswith("compare_all") and cap > (128 << 10):
                continue  # a (cap, values) comparison per page
            row = {"shape": name, "cap": cap, "form": form}
            if how == "map":
                def prog(do, dd, idx, nn, fn=fn, cap=cap):
                    return jax.lax.map(
                        lambda p: fn(do, dd, p[0], p[1], cap), (idx, nn))
            else:
                def prog(do, dd, idx, nn, fn=fn, cap=cap):
                    return fn(do, dd, idx, nn[:, None], cap)
            try:
                t0 = time.perf_counter()
                comp = jax.jit(prog).lower(*dev).compile()
                row["compile_s"] = time.perf_counter() - t0
                got = np.asarray(jax.block_until_ready(comp(*dev)))
            except Exception as e:  # a form the chip cannot run
                row["error"] = f"{type(e).__name__}: {e}"[:300]
                out["rows"].append(row)
                print(json.dumps(row), flush=True)
                continue
            if want is None:
                want = got
            ok = all((got[p, :t] == want[p, :t]).all() for p, t in
                     enumerate(lens[idx[:, :VALUES]].sum(axis=1)))
            row["bit_exact"] = bool(ok)
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(comp(*dev))
                times.append(time.perf_counter() - t0)
            row["ms"] = statistics.median(times) * 1e3
            row["ms_per_page"] = row["ms"] / args.pages
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    return out


def columns(args) -> dict:
    sys.path.insert(0, args.root)
    sys.path.insert(1, HERE)
    import jax

    from benchmark.harness import load
    from tpuparquet.io.reader import FileReader
    from tpuparquet.kernels import device as D

    gen = load(os.path.join(HERE, "benchmark", "configs",
                            "tpch-lineitem-sf1", "generate.py"),
               "lineitem_generate")
    work = os.path.join(args.root, ".bench_work", "columns")
    os.makedirs(work, exist_ok=True)
    path = gen.generate(2700000101, work, scale=args.scale)[0][0]
    times = {}
    chunk_column = D._chunk_column

    def timed(plan, *a):
        t0 = time.perf_counter()
        col = jax.block_until_ready(chunk_column(plan, *a))
        times.setdefault(plan.kinds, []).append(time.perf_counter() - t0)
        return col

    D._chunk_column = timed
    out = {"device": jax.devices()[0].device_kind, "root": args.root,
           "columns": {}}
    try:
        import pyarrow.parquet as pq

        for c in pq.ParquetFile(path).schema_arrow.names:
            ms = []
            for k in range(args.reps + 1):  # the first compiles
                times.clear()
                with FileReader(path, c) as r:
                    D.read_row_group_device(r, 0)
                if k:
                    ms.append(sum(sum(v) for v in times.values()) * 1e3)
            out["columns"][c] = {"kinds": ",".join(sorted(times)),
                                 "ms": statistics.median(ms)}
            print(json.dumps({c: out["columns"][c]}), flush=True)
    finally:
        D._chunk_column = chunk_column
    out["total_ms"] = sum(v["ms"] for v in out["columns"].values())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("kernels", "columns"))
    ap.add_argument("--pages", type=int, default=50)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--scale", type=float, default=0.35)
    args = ap.parse_args(argv)
    out = kernels(args) if args.mode == "kernels" else columns(args)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
