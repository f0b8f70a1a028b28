"""Seeded NYC TLC Yellow Taxi trip records, Jan-Mar 2023 (spec.json).

The 19 published columns, in the published order and types, at the
published row counts.  The value distributions are assumed (spec.json
``assumed``): shapes in the style of the real months, drawn with numpy
from a fixed stream, then moved by the seed (``month``).  Money is
whole cents, as the meter rounds it.
"""

from __future__ import annotations

import json
import os

import numpy as np

from benchmark.datagen import Column, pick, relabel, strings_from_pool, \
    write_parquet

SPEC = json.load(open(os.path.join(os.path.dirname(__file__),
                                   "spec.json")))
_MONTH_START_US = {"2023-01": 1672531200_000000,
                   "2023-02": 1675209600_000000,
                   "2023-03": 1677628800_000000}
_MONTH_DAYS = {"2023-01": 31, "2023-02": 28, "2023-03": 31}


def _cents(x: np.ndarray) -> np.ndarray:
    """Dollar amounts as whole cents."""
    return np.rint(x * 100.0).astype(np.int64)


def month(seed: int, index: int, rows: int, name: str) -> dict:
    """One month: a fixed draw (``skel``) that no seed changes, with
    every column's values moved by a seeded bijection (``relabel``,
    ``pick``).  Every seed so writes the same nulls, dictionaries, runs
    and pages, and the seed changes which value each row holds."""
    skel = np.random.default_rng([0x7A1, index])
    rng = np.random.default_rng([seed, index])
    n = rows
    # rows with no passenger/rate/flag/surcharge data (the "unknown"
    # rows of the published files, payment_type 0)
    unknown = skel.random(n) < 0.0234
    known = ~unknown

    vendor = pick(rng, [1, 2], (skel.random(n) >= 0.26).astype(np.int64))
    span_s = _MONTH_DAYS[name] * 86_400
    pick_s = skel.integers(0, span_s, n)
    dur_s = np.clip(skel.lognormal(np.log(720.0), 0.6, n), 1, 36_000)
    drop_s = pick_s + dur_s.astype(np.int64)

    passengers = pick(rng, np.arange(7, dtype=np.float64),
                      skel.choice(7, n, p=[.02, .73, .15, .04, .02,
                                            .02, .02]))
    dist = _cents(np.clip(skel.lognormal(np.log(1.8), 0.9, n), 0, 200))
    ratecode = pick(rng, np.array([1, 2, 3, 4, 5, 99], np.float64),
                    skel.choice(6, n, p=[.94, .04, .004, .002, .006,
                                         .008]))
    flag = skel.random(n) < 0.006
    # 265 zones, a few of them busy (a Zipf-like popularity)
    zone_p = 1.0 / np.arange(1, 266) ** 1.1
    zone_p /= zone_p.sum()
    zones = np.arange(1, 266, dtype=np.int64)
    pu = pick(rng, zones, skel.choice(265, n, p=zone_p))
    do = pick(rng, zones, skel.choice(265, n, p=zone_p))
    card = skel.choice(4, n, p=[.79, .19, .01, .01])
    payment = pick(rng, np.arange(5, dtype=np.int64),
                   np.where(unknown, 0, card + 1))

    fare = _cents(3.0 + 3.5 * dist / 100 + dur_s / 60.0 * 0.7)
    extra = _cents(skel.choice(np.array([0.0, 1.0, 2.5, 3.5, 5.0]), n,
                               p=[.45, .2, .2, .1, .05]))
    mta = np.where(skel.random(n) < 0.99, 50, 0)
    tip = np.where(card == 0,
                   np.rint(fare * skel.uniform(0.1, 0.3, n)), 0)
    tolls = np.where(skel.random(n) < 0.08, 655, 0)
    improvement = np.where(skel.random(n) < 0.97, 100, 30)
    congestion = np.where(skel.random(n) < 0.92, 250, 0)
    airport = np.isin(skel.choice(265, n, p=zone_p), [0, 1]) * 125
    total = (fare + extra + mta + tip + tolls + improvement
             + np.where(known, congestion + airport, 0))

    def money(cents, nullable=False):
        v = relabel(rng, cents) / 100.0
        if nullable:
            return Column("double", values=np.where(known, v, 0.0),
                          valid=known.copy())
        return Column("double", values=v)

    def stamps(secs):
        return Column("timestamp_us", values=(
            _MONTH_START_US[name] + relabel(rng, secs) * 1_000_000))

    flag_offs, flag_data = strings_from_pool(
        [b"N", b"Y"], pick(rng, [0, 1], flag.astype(np.int64)), known)
    return {
        "VendorID": Column("int64", values=vendor),
        "tpep_pickup_datetime": stamps(pick_s),
        "tpep_dropoff_datetime": stamps(drop_s),
        "passenger_count": Column("double", values=np.where(
            known, passengers, 0.0), valid=known.copy()),
        "trip_distance": money(dist),
        "RatecodeID": Column("double", values=np.where(
            known, ratecode, 0.0), valid=known.copy()),
        "store_and_fwd_flag": Column("string", offsets=flag_offs,
                                     data=flag_data, valid=known.copy()),
        "PULocationID": Column("int64", values=pu),
        "DOLocationID": Column("int64", values=do),
        "payment_type": Column("int64", values=payment),
        "fare_amount": money(fare),
        "extra": money(extra),
        "mta_tax": money(mta),
        "tip_amount": money(tip.astype(np.int64)),
        "tolls_amount": money(tolls),
        "improvement_surcharge": money(improvement),
        "total_amount": money(total.astype(np.int64)),
        "congestion_surcharge": money(congestion, True),
        "Airport_fee": money(airport, True),
    }


def generate(seed: int, out_dir: str, scale: float = 1.0) -> list:
    """Write the month files; returns ``[(path, rows, columns)]``.
    ``scale`` shrinks the row counts for the CPU tests only."""
    out = []
    for i, (name, rows) in enumerate(SPEC["deployment"]["months"]):
        rows = max(int(rows * scale), 1)
        cols = month(seed, i, rows, name)
        path = os.path.join(out_dir, f"yellow_tripdata_{name}.parquet")
        write_parquet(path, cols, SPEC["writer"])
        out.append((path, rows, cols))
    return out
