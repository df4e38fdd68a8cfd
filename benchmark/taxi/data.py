"""NYC taxi trips, made from a seed by the shape of the TLC's trip records.

The New York City Taxi & Limousine Commission publishes every yellow and
green cab trip; the "1.1 billion taxi rides" benchmark queries 2009-2015
of them. A row here is one trip, with the seven fields the statements of
``shapes.json`` read: the cab type, the passenger count, the pickup time
(milliseconds since 1970, UTC), the distance in miles, the fare, the
total paid and the pickup zone. ``ASSUMED`` lists every rule, written
from memory of the data dictionary and of published summaries of the
data, to be checked against them.

Every amount and distance is dealt as an integer of hundredths (cents,
hundredths of a mile) and handed over as the correctly rounded double of
its two-place decimal (an int64 over 100.0 is one IEEE division of two
exact operands), so the reference recovers the integer exactly and sums
in integers. Each segment holds trips of the whole period, in pickup
order, and reaches the ends of each range a deployment holds (2009 and
2015, a distance of 0 and one near the 200-mile cut, a fare near the
400-dollar cut): at 2^23 rows a segment meets them by chance, and a
small test table is held to them, so that every segment's column
metadata bounds the same key spaces and the segments share one plan.

Nothing of the program is in here: a column is a plain ``numpy`` array,
or a ``Coded`` pair of integer codes and the values they index.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.ssb.data import Coded, Column

# pickup times: 2009-01-01T00:00Z up to 2016-01-01T00:00Z, in ms
FIRST_MS = 1_230_768_000_000
END_MS = 1_451_606_400_000
CAB_TYPES = ["green", "yellow"]                 # sorted, as a dictionary
ZONES = 265                                     # TLC taxi zones 1..265
# passenger_count 0..9 and its shares
PASSENGER_SHARES = (0.004, 0.70, 0.14, 0.04, 0.02, 0.06, 0.0355,
                    0.0002, 0.0002, 0.0001)
DISTANCE_CAP = 20_000                           # hundredths of a mile
FARE_CAP = 40_000                               # cents

# the DOUBLE measures (the rest are dimensions)
MEASURES = ("trip_distance", "fare_amount", "total_amount")

ASSUMED = {
    "cab_type": "yellow 85 %, green 15 %",
    "passenger_count": "0-9 with shares 0.4, 70, 14, 4, 2, 6, 3.55, 0.02, "
                       "0.02, 0.01 %",
    "pickup_datetime": "uniform over 2009-01-01 .. 2015-12-31 UTC, "
                       "rows in pickup order within a segment",
    "trip_distance": "two decimals, miles: 1 % exactly 0 (no movement), "
                     "0.1 % uniform over [0, 200) (bad readings a "
                     "deployment keeps after cutting at 200), the rest "
                     "lognormal with median 1.6 and sigma 0.8, cut to "
                     "[0.01, 199.99]",
    "fare_amount": "two decimals: 0.1 % uniform over [2.50, 400), the "
                   "rest lognormal with median 9.50 and sigma 0.55, cut "
                   "to [2.50, 399.99]",
    "total_amount": "fare + 0.50 MTA tax + a surcharge of 0, 0.50 or 1.00 "
                    "+ a tip (none on 40 %, else 10-25 % of the fare, to "
                    "the cent) + tolls of 5.54 on 5 %",
    "pu_location_id": "zones 1-265, Zipf popularity (s = 1) over a fixed "
                      "shuffle of the ids: a few zones take most pickups",
    "streams": "numpy's generator seeded by (seed, segment), not the "
               "TLC's records",
}


def _zone_weights() -> np.ndarray:
    """Zipf (s = 1) popularity of the 265 zones, over a fixed shuffle of
    the ids (the same for every seed: the city's geography)."""
    rank = np.random.default_rng(265).permutation(ZONES) + 1.0
    w = 1.0 / rank
    return w / w.sum()


def gen_segment(n: int, seed: int, segment: int,
                at_most=()) -> Dict[str, Column]:
    """``n`` rows for segment ``segment`` of the table ``seed`` names, in
    pickup order. ``at_most`` is the harness's ``segment_rows_at_most``;
    this configuration has none."""
    if at_most:
        raise ValueError("taxi: no segment_rows_at_most is defined")
    rng = np.random.default_rng((seed, segment, 39))
    pickup = np.sort(rng.integers(FIRST_MS, END_MS, n))
    cab = (rng.random(n) < 0.85).astype(np.int8)          # 1 -> yellow
    passengers = rng.choice(10, n, p=PASSENGER_SHARES).astype(np.int32)

    miles = np.rint(np.exp(rng.normal(np.log(1.6), 0.8, n)) * 100)
    miles = np.clip(miles, 1, DISTANCE_CAP - 1).astype(np.int64)
    kind = rng.random(n)
    miles[kind < 0.01] = 0
    bad = kind > 0.999
    miles[bad] = rng.integers(0, DISTANCE_CAP, int(bad.sum()))

    fare = np.rint(np.exp(rng.normal(np.log(9.5), 0.55, n)) * 100)
    fare = np.clip(fare, 250, FARE_CAP - 1).astype(np.int64)
    tail = rng.random(n) < 0.001
    fare[tail] = rng.integers(250, FARE_CAP, int(tail.sum()))
    tip = np.where(rng.random(n) < 0.4, 0,
                   np.rint(fare * rng.uniform(0.10, 0.25, n))).astype(
                       np.int64)
    total = (fare + 50 + 50 * rng.integers(0, 3, n) + tip
             + np.where(rng.random(n) < 0.05, 554, 0))
    zone = (rng.choice(ZONES, n, p=_zone_weights()) + 1).astype(np.int32)
    # each segment reaches the ends of the ranges a deployment holds
    pickup[0], pickup[-1] = FIRST_MS, END_MS - 1
    miles[:2] = (0, DISTANCE_CAP - 1)
    fare[0] = FARE_CAP - 1
    total[0] = fare[0] + 50 + tip[0]
    return {
        "cab_type": Coded(cab, CAB_TYPES),
        "passenger_count": passengers,
        "pickup_datetime": pickup.astype(np.int64),
        "trip_distance": miles / 100.0,
        "fare_amount": fare / 100.0,
        "total_amount": total / 100.0,
        "pu_location_id": zone,
    }
