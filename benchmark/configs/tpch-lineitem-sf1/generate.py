"""Seeded TPC-H ``lineitem`` at scale factor 1 (spec.json).

The 16 columns and the value rules of the TPC-H specification (4.2.3),
drawn with numpy from a fixed stream: orders in key order with 1-7
lines each, prices from the part key, dates from the order date, flags
from the current date.  The seed then moves every column's values but
the keys' (``part``), and writes the comment text: a window into a
seeded word stream (spec.json ``assumed``), not dbgen's grammar.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmark.datagen import Column, _ranges, pick_same_length, \
    relabel, strings_from_pool, write_parquet

SPEC = json.load(open(os.path.join(os.path.dirname(__file__),
                                   "spec.json")))
_EPOCH = np.datetime64("1970-01-01")
START = int((np.datetime64("1992-01-01") - _EPOCH).astype(int))
END = int((np.datetime64("1998-12-31") - _EPOCH).astype(int))
CURRENT = int((np.datetime64("1995-06-17") - _EPOCH).astype(int))
INSTRUCT = [b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
            b"TAKE BACK RETURN"]
MODES = [b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"]
WORDS = (b"furiously quickly carefully blithely slyly ironically finally "
         b"regularly express special pending bold final ironic regular "
         b"unusual even silent daring fluffily packages requests accounts "
         b"deposits foxes ideas theodolites pinto beans instructions "
         b"dependencies excuses platelets asymptotes courts dolphins "
         b"multipliers sauternes warthogs frets dinos attainments somas "
         b"tithes sentiments decoys realms pains grouches escapades "
         b"sleep wake are cajole haggle nag use boost affix detect "
         b"integrate maintain nod was lose sublate solve thrash promise "
         b"engage hinder print x-ray breach eat grow impress mold poach "
         b"serve run dazzle snooze doze unwind kindle play hang believe "
         b"doubt about above according across after against along among "
         b"around at atop before behind beneath beside besides between "
         b"beyond by despite during except for from in inside instead of "
         b"into near of on outside over past since through throughout to "
         b"toward under until up upon without with within the").split()


def _line_counts(skel, orders: int, rows: int) -> np.ndarray:
    """1-7 lines per order, nudged until they sum to ``rows``."""
    c = skel.integers(1, 8, orders)
    diff = rows - int(c.sum())
    while diff:
        room = np.flatnonzero(c < 7) if diff > 0 else np.flatnonzero(c > 1)
        pick_ = skel.choice(room, min(abs(diff), len(room)), replace=False)
        c[pick_] += np.sign(diff)
        diff = rows - int(c.sum())
    return c


def _comments(skel, rng, n: int):
    """Comments of 10-43 characters: a 5-letter tag from a seeded
    permutation, which keeps every comment of a file distinct, then a
    window into a seeded stream of words.  The lengths are the fixed
    draw's, so the dictionary overflows at the same row and every page
    holds the same bytes whatever the seed."""
    lens = skel.integers(10, 44, n)
    tag = rng.permutation(n)
    words = rng.choice(len(WORDS), 1 << 19)
    stream = np.frombuffer(b" ".join(WORDS[w] for w in words),
                           dtype=np.uint8)
    starts = rng.integers(0, len(stream) - 44, n)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    data = stream[_ranges(starts, lens)]
    for k in range(5):
        data[offs[:-1] + k] = ord("a") + tag // 26 ** k % 26
    data[offs[:-1] + 5] = ord(" ")
    return offs, data


def part(seed: int, index: int, first_order: int, orders: int,
         rows: int) -> dict:
    """One ``dbgen -C 2`` part: a fixed draw (``skel``) that no seed
    changes, with every column but the keys moved by a seeded bijection
    (``relabel``, ``pick_same_length``).  Every seed so writes the same
    dictionaries, runs and pages, and the seed changes which value each
    row holds."""
    skel = np.random.default_rng([0x11E, index])
    rng = np.random.default_rng([seed, index])
    lines = _line_counts(skel, orders, rows)
    oi = np.arange(first_order, first_order + orders, dtype=np.int64)
    # dbgen's sparse keys: 8 of every 32
    okey = (oi // 8) * 32 + oi % 8 + 1
    odate = skel.integers(START, END - 151 + 1, orders)
    orderkey = np.repeat(okey, lines)
    orderdate = np.repeat(odate, lines)
    starts = np.repeat(np.cumsum(lines) - lines, lines)
    linenumber = (np.arange(rows) - starts + 1).astype(np.int32)

    partkey = skel.integers(1, 200_001, rows)
    s = 10_000
    suppkey = (partkey + skel.integers(0, 4, rows)
               * (s // 4 + (partkey - 1) // s)) % s + 1
    quantity = skel.integers(1, 51, rows)
    retail = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)
    shipdate = orderdate + skel.integers(1, 122, rows)
    commitdate = orderdate + skel.integers(30, 91, rows)
    receiptdate = shipdate + skel.integers(1, 31, rows)
    returned = receiptdate <= CURRENT
    rflag = np.where(returned, skel.integers(0, 2, rows), 2)
    lstatus = (shipdate > CURRENT).astype(np.int64)

    def text(pool, codes):
        offs, data = strings_from_pool(pool, pick_same_length(
            rng, pool, codes))
        return Column("string", offsets=offs, data=data, nullable=False)

    def fixed(arrow, v):
        return Column(arrow, values=np.ascontiguousarray(v),
                      nullable=False)

    def moved(arrow, v):
        return fixed(arrow, relabel(rng, v).astype(v.dtype))

    c_offs, c_data = _comments(skel, rng, rows)
    return {
        "l_orderkey": fixed("int64", orderkey),
        "l_partkey": moved("int64", partkey),
        "l_suppkey": moved("int64", suppkey),
        "l_linenumber": fixed("int32", linenumber),
        "l_quantity": moved("decimal(15,2)", quantity * 100),
        "l_extendedprice": moved("decimal(15,2)", quantity * retail),
        "l_discount": moved("decimal(15,2)", skel.integers(0, 11, rows)),
        "l_tax": moved("decimal(15,2)", skel.integers(0, 9, rows)),
        "l_returnflag": text([b"R", b"A", b"N"], rflag),
        "l_linestatus": text([b"F", b"O"], lstatus),
        "l_shipdate": moved("date32", shipdate.astype(np.int32)),
        "l_commitdate": moved("date32", commitdate.astype(np.int32)),
        "l_receiptdate": moved("date32", receiptdate.astype(np.int32)),
        "l_shipinstruct": text(INSTRUCT, skel.integers(0, 4, rows)),
        "l_shipmode": text(MODES, skel.integers(0, 7, rows)),
        "l_comment": Column("string", offsets=c_offs, data=c_data,
                            nullable=False),
    }


def generate(seed: int, out_dir: str, scale: float = 1.0) -> list:
    """Write the ``dbgen -C 2`` parts; returns
    ``[(path, rows, columns)]``.  ``scale`` shrinks the row counts for
    the CPU tests only."""
    dep = SPEC["deployment"]
    out = []
    first = 0
    for i, (orders, rows) in enumerate(dep["parts"]):
        orders = max(int(orders * scale), 1)
        rows = min(max(int(rows * scale), orders), 7 * orders)
        cols = part(seed, i, first, orders, rows)
        first += orders
        path = os.path.join(out_dir, f"lineitem.tbl.{i + 1}.parquet")
        write_parquet(path, cols, SPEC["writer"])
        out.append((path, rows, cols))
    return out
