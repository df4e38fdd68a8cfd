"""The plain reference: each statement's COUNT(*) by its keys over the
whole table, by ``numpy.unique(..., return_counts=True)`` over the
packed key VALUES of the host columns the seed made. It imports nothing
of the program and reads nothing the program produced.

A phrase becomes its rank among the table's distinct phrases (the sorted
union of every segment's), so equal text is one key whichever segment
holds it; a key tuple packs mixed-radix into one int64 of each key's
rank among its distinct values.

``answer`` keeps every group whose count reaches the LIMIT-th count: an
``ORDER BY c DESC LIMIT n`` may answer any of the groups tied at the
boundary, in any order among equal counts, and ``same`` accepts exactly
those answers.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from benchmark.ssb.data import Coded, Column


def _phrases(segments: Sequence[Dict[str, Column]]):
    """(each segment's rows as ranks among the table's distinct phrases,
    those phrases sorted)."""
    coded: List[Coded] = [seg["SearchPhrase"] for seg in segments]
    vocab = np.unique(np.concatenate([np.asarray(c.values, dtype=str)
                                      for c in coded]))
    return [np.searchsorted(vocab, np.asarray(c.values, dtype=str))[c.codes]
            for c in coded], vocab


def _key_column(segments, key: str, phrases):
    """(the key over the table as non-negative int64, its decoder)."""
    if key == "SearchPhrase":
        ranks, vocab = phrases
        return np.concatenate(ranks).astype(np.int64), vocab.tolist()
    col = np.concatenate([seg[key] for seg in segments]).astype(np.int64)
    values, inverse = np.unique(col, return_inverse=True)
    return inverse.ravel().astype(np.int64), values.tolist()


def answer(segments: Sequence[Dict[str, Column]], shape) -> Dict:
    """{"rows": every (keys..., count) whose count reaches the LIMIT-th
    count, by count descending then key; "cut": that count; "groups":
    the number of groups in the table}."""
    phrases = _phrases(segments) if "SearchPhrase" in shape["keys"] \
        or shape["nonempty_phrase"] else None
    code = None
    decoders = []
    for key in shape["keys"]:
        col, values = _key_column(segments, key, phrases)
        code = col if code is None else code * len(values) + col
        decoders.append(values)
    ranks, vocab = phrases or ([], [])
    if shape["nonempty_phrase"] and len(vocab) and vocab[0] == "":
        code = code[np.concatenate(ranks) != 0]      # "" sorts first
    groups, counts = np.unique(code, return_counts=True)
    limit = shape["limit"]
    cut = int(np.partition(counts, len(counts) - limit)[len(counts) - limit]) \
        if len(counts) > limit else (int(counts.min()) if len(counts) else 0)
    top = np.nonzero(counts >= cut)[0]
    rows = []
    for g, c in zip(groups[top].tolist(), counts[top].tolist()):
        key = []
        for values in reversed(decoders):
            key.append(values[g % len(values)])
            g //= len(values)
        rows.append(tuple(reversed(key)) + (c,))
    rows.sort(key=lambda r: (-r[-1], r[:-1]))
    return {"rows": rows, "cut": cut, "groups": len(counts)}


def same(got, expected: Dict, shape) -> bool:
    """Did the system answer ``shape`` as its ORDER BY and LIMIT allow:
    min(LIMIT, groups) distinct groups, counts non-increasing, each with
    its exact count among the reference's rows, and every group whose
    count is above the LIMIT-th count present."""
    if got is None:
        return False
    got = [tuple(r) for r in got]
    if len(got) != min(shape["limit"], expected["groups"]):
        return False
    counts = [r[-1] for r in got]
    if any(a < b for a, b in zip(counts, counts[1:])):
        return False
    allowed = {r[:-1]: r[-1] for r in expected["rows"]}
    keys = [r[:-1] for r in got]
    if len(set(keys)) != len(keys) or any(
            allowed.get(k) != c for k, c in zip(keys, counts)):
        return False
    must = {k for k, c in allowed.items() if c > expected["cut"]}
    return must <= set(keys)
