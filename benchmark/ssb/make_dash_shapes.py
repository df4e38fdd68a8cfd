"""Draw ``shapes_dash.json``: the 13 statements of ``shapes.json`` and
three literal variants of each, for the dashboard mix (``dash_c8``).

    python3 benchmark/ssb/make_dash_shapes.py [--seed 33] [--out FILE]

The rule is the one of the benchmark SSB derives from: TPC-H's throughput
test runs several query streams at once, each with its own substitution
parameters. A variant keeps its shape's ``flight``, ``value``, ``group``,
``order`` and every predicate's column and operator, and draws the
literals again:

- ``eq``: another value of the column's domain;
- ``between``: another range of the same width inside the domain, which
  touches an end of the domain only where the source's does (the planner
  lowers a range that starts at the column's least value to a one-sided
  one: another plan structure); for ``p_brand`` the same count of brands
  of one category, between two four-digit brands, so that the strings'
  order and the brands' agree;
- ``in``: another set of the same count (cities: of one nation, as the
  source's two are; a set the shape uses twice is drawn once);
- ``lt``: kept.

A year drawn for ``eq``, in a set or as a range's end is one of 1992-1997
(1998 is a partial year in dbgen), a week one of 1-52 (week 53 has one or
two days). No two variants of a shape are equal. So the 52 statements
have 13 plan structures, and selectivities equal to the source's by the
keys' odds. The file is in variant-major order: the 13 source shapes,
then variant 1 of all 13 (``q1.1.v1`` ... ``q4.3.v1``), then ``.v2``,
``.v3``. The draws use ``random.Random(seed)``'s ``randrange`` and
``sample`` only; ``benchmark/tests/test_dash_shapes.py`` holds the file
to this script.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.ssb import data  # noqa: E402

SEED = 33
VARIANTS = 3                    # besides the source's own literals
YEARS = list(range(1992, 1998))
INT_DOMAINS = {"lo_discount": (0, 10), "lo_quantity": (1, 50),
               "d_year": (1992, 1998)}
STR_DOMAINS = {"region": data.REGIONS, "nation": data.NATIONS,
               "city": data.CITIES, "category": data.CATEGORIES,
               "brand": data.BRANDS, "mfgr": data.MFGRS}


def _pick(rng: random.Random, seq):
    return seq[rng.randrange(len(seq))]


def _domain(col: str) -> List[str]:
    return STR_DOMAINS[col.split("_", 1)[1]]


def draw(rng: random.Random, col: str, op: str, val) -> Any:
    """Literals for one predicate, by the module's rule."""
    if op == "lt":
        return val
    if op == "eq":
        if col == "d_year":
            return _pick(rng, YEARS)
        if col == "d_yearmonthnum":
            return _pick(rng, YEARS) * 100 + rng.randrange(1, 13)
        if col == "d_weeknuminyear":
            return rng.randrange(1, 53)
        return _pick(rng, _domain(col))
    if op == "between":
        if col == "p_brand":
            # MFGR#<m><c><b>, b = 10t + u: [..tu, ..t(u+n-1)] holds the
            # same n brands as strings and as brands
            n = int(val[1][-1]) - int(val[0][-1]) + 1
            cat, t = _pick(rng, data.CATEGORIES), rng.randrange(1, 4)
            u = rng.randrange(0, 10 - n + 1)
            return [f"{cat}{t}{u}", f"{cat}{t}{u + n - 1}"]
        lo, hi = INT_DOMAINS[col]
        width = val[1] - val[0]
        last = YEARS[-1] if col == "d_year" else hi
        start = _pick(rng, [
            a for a in range(lo, last - width + 1)
            if (a == lo) == (val[0] == lo)
            and (a + width == hi) == (val[1] == hi)])
        return [start, start + width]
    if op == "in":
        if col == "d_year":
            return sorted(rng.sample(YEARS, len(val)))
        if col.endswith("_city"):
            nation = _pick(rng, data.NATIONS)[:9].ljust(9)
            return [nation + str(d)
                    for d in sorted(rng.sample(range(10), len(val)))]
        return sorted(rng.sample(_domain(col), len(val)))
    raise ValueError(f"unknown predicate op {op!r}")


def variant(rng: random.Random, shape: Dict[str, Any], n: int
            ) -> Dict[str, Any]:
    sets: Dict[str, Any] = {}       # a set the shape uses twice: one draw
    preds = []
    for col, op, val in shape["preds"]:
        if op == "in":
            key = json.dumps(val)
            if key not in sets:
                sets[key] = draw(rng, col, op, val)
            preds.append([col, op, sets[key]])
        else:
            preds.append([col, op, draw(rng, col, op, val)])
    return {**shape, "id": f"{shape['id']}.v{n}", "preds": preds}


def make(seed: int = SEED) -> Dict[str, Any]:
    with open(os.path.join(HERE, "shapes.json")) as f:
        src = json.load(f)
    rng = random.Random(seed)
    rounds: List[List[Dict[str, Any]]] = [src["shapes"]]
    seen = {s["id"]: [s["preds"]] for s in src["shapes"]}
    for n in range(1, VARIANTS + 1):
        drawn = []
        for shape in src["shapes"]:
            v = variant(rng, shape, n)
            while v["preds"] in seen[shape["id"]]:
                v = variant(rng, shape, n)
            seen[shape["id"]].append(v["preds"])
            drawn.append(v)
        rounds.append(drawn)
    return {
        "source": src["source"] + "; literal variants by "
                  "benchmark/ssb/make_dash_shapes.py (TPC-H's rule for "
                  "concurrent query streams: each its own substitution "
                  "parameters)",
        "table": src["table"], "seed": seed,
        "order": "variant-major: the 13 source shapes, then .v1 of all "
                 "13, .v2, .v3",
        "shapes": [s for r in rounds for s in r]}


def dumps(doc: Dict[str, Any]) -> str:
    """One shape a line, as ``shapes.json`` is read by eye."""
    head = {k: v for k, v in doc.items() if k != "shapes"}
    lines = json.dumps(head, indent=1)[:-2] + ',\n "shapes": [\n'
    lines += ",\n".join("  " + json.dumps(s) for s in doc["shapes"])
    return lines + "\n ]\n}\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--out", default=os.path.join(HERE, "shapes_dash.json"))
    args = ap.parse_args(argv)
    with open(args.out, "w") as f:
        f.write(dumps(make(args.seed)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
