"""ClickBench's ``hits`` table, made from a seed: the three columns its
top-10 group-by statements read.

ClickBench's table is a day of Yandex.Metrica page views: 99,997,497 rows,
17,630,976 distinct ``UserID``s, 6,019,103 distinct ``SearchPhrase``s.
A row here is one hit with its user, its search phrase (empty where the
hit came from no search) and the search engine that sent it (0 where
none did). ``ASSUMED`` lists every rule, from memory of the benchmark's
published answers, to be checked against them.

The rows are cut, the key spaces are not: every one of the source's
users and phrases is in the table. Segment ``k`` of ``SEGMENTS`` holds
the first hit of each user and each phrase whose rank is ``k`` modulo
``SEGMENTS`` (as many as its rows allow: a small test table holds a
share of each population, ``FIRST_HIT_SHARE`` of its rows), and the
rest of its rows draw users and phrases by heavy-tailed popularity over
the whole population. A user's or phrase's rank is fixed by the
deployment; the seed draws the rows and which id each rank gets.

A phrase is text made from its rank (three pseudo-words), so every
segment spells it the same way; a segment hands its phrases over as a
``Coded`` pair of codes and the distinct phrases the segment holds, in
fixed-width numpy unicode. Nothing of the program is in here.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.ssb.data import Coded, Column

SOURCE_ROWS = 99_997_497
USERS = 17_630_976
PHRASES = 6_019_103
SEGMENTS = 8
NONEMPTY_SHARE = 0.13          # rows with a search phrase
ENGINES = 39                   # SearchEngineID 1..39 of search hits
# share of a segment's rows that are some user's, or some phrase's,
# first hit: at 2^23 rows a segment, 8 segments hold every user
# (2,203,872 a segment, 26.3 % of its rows) and every phrase (752,388)
FIRST_HIT_SHARE = {"users": 0.27, "phrases": 0.09}
# Zipf exponents of the popularity ranks the other rows draw from
USER_ZIPF = 0.5
PHRASE_ZIPF = 0.6
ENGINE_ZIPF = 1.5

MEASURES = ()                  # every column is a dimension

ASSUMED = {
    "UserID": "17,630,976 users (the source's count), each with a first "
              "hit in the segment of its rank modulo 8; the other hits "
              "draw users by rank r with weight r^-0.5 (a heavy tail: "
              "the busiest user about 12,000 hits of 2^26); the id of a "
              "rank is a 63-bit hash of it and the seed",
    "SearchPhrase": "empty on 87 % of hits; the other 13 % hold the "
                    "source's 6,019,103 phrases, each once, and draws "
                    "by rank with weight r^-0.6",
    "SearchEngineID": "0 where the phrase is empty, else 1..39 with "
                      "Zipf (s = 1.5) shares",
    "order": "rows in generation order within a segment (the source "
             "sorts by CounterID, EventDate, UserID, EventTime; no "
             "statement here reads order)",
    "streams": "numpy's generator seeded by (seed, segment), not the "
               "source's rows",
}

# pseudo-words a phrase is spelt from: 32 x 32 x 2 syllable triples
_SYL = np.array(["ka", "lo", "mi", "ne", "su", "ta", "vo", "ri", "pa",
                 "de", "go", "ju", "be", "xi", "zo", "fa", "ho", "ly",
                 "wu", "qe", "sa", "no", "ki", "mu", "te", "ro", "va",
                 "di", "po", "ge", "bu", "ze"])
_WORDS = np.char.add(np.char.add(_SYL[np.arange(2048) % 32],
                                 _SYL[(np.arange(2048) // 32) % 32]),
                     np.array(["n", "s"])[np.arange(2048) // 1024])


def phrase_text(rank: np.ndarray) -> np.ndarray:
    """The phrase of each rank, as numpy unicode (distinct ranks give
    distinct text below 2048^3)."""
    rank = np.asarray(rank, dtype=np.int64)
    return np.char.add(np.char.add(
        np.char.add(_WORDS[rank % 2048], " "),
        np.char.add(_WORDS[(rank >> 11) % 2048], " ")),
        _WORDS[rank >> 22])


def _user_ids(rank: np.ndarray, seed: int) -> np.ndarray:
    """A 63-bit id a user rank (splitmix64 of the rank mixed with the
    seed, top bit cleared)."""
    x = (rank.astype(np.uint64)
         + np.uint64((seed * 0x9E3779B97F4A7C15) % (1 << 64)))
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return (x >> np.uint64(1)).astype(np.int64)


def _zipf_ranks(rng, population: int, alpha: float, n: int) -> np.ndarray:
    """``n`` ranks in [0, population), rank r drawn with weight about
    r^-alpha (the inverse of the continuous law's distribution)."""
    r = rng.random(n)
    return np.minimum((population * r ** (1.0 / (1.0 - alpha))).astype(
        np.int64), population - 1)


def _firsts(population: int, segment: int, at_most: int) -> np.ndarray:
    """Ranks whose first hit segment ``segment`` holds."""
    return np.arange(segment, population, SEGMENTS, dtype=np.int64)[:at_most]


def gen_segment(n: int, seed: int, segment: int,
                at_most=()) -> Dict[str, Column]:
    """``n`` rows for segment ``segment`` of the table ``seed`` names.
    ``at_most`` is the harness's ``segment_rows_at_most``; this
    configuration has none."""
    if at_most:
        raise ValueError("hits: no segment_rows_at_most is defined")
    rng = np.random.default_rng((seed % (1 << 63), segment, 42))
    users_here = min(-(-USERS // SEGMENTS),
                     int(n * FIRST_HIT_SHARE["users"]))
    phrases_here = min(-(-PHRASES // SEGMENTS),
                       int(n * FIRST_HIT_SHARE["phrases"]))
    users = min(USERS, users_here * SEGMENTS)
    phrases = min(PHRASES, phrases_here * SEGMENTS)

    first_users = _firsts(users, segment, users_here)
    user_rank = np.concatenate([
        first_users, _zipf_ranks(rng, users, USER_ZIPF,
                                 n - len(first_users))])
    searched = np.zeros(n, dtype=bool)
    first_phrases = _firsts(phrases, segment, phrases_here)
    n_search = max(int(round(n * NONEMPTY_SHARE)), len(first_phrases))
    searched[rng.choice(n, n_search, replace=False)] = True
    phrase_rank = np.full(n, -1, dtype=np.int64)
    phrase_rank[searched] = rng.permutation(np.concatenate([
        first_phrases, _zipf_ranks(rng, phrases, PHRASE_ZIPF,
                                   n_search - len(first_phrases))]))

    weights = 1.0 / np.arange(1, ENGINES + 1) ** ENGINE_ZIPF
    engine = np.where(searched, rng.choice(
        ENGINES, n, p=weights / weights.sum()) + 1, 0).astype(np.int32)

    ranks, codes = np.unique(phrase_rank, return_inverse=True)
    text = phrase_text(np.maximum(ranks, 0))
    text[ranks < 0] = ""
    return {
        "UserID": _user_ids(user_rank, seed),
        "SearchPhrase": Coded(codes.astype(np.int32), text),
        "SearchEngineID": engine,
    }
