"""Per request, the wall milliseconds the program spent in some of its
metered phases (``pinot_tpu/utils/spans.phase``): the window's delta of
the counters ``phase_us_<p>`` summed over ``phases``, less those of
``minus``. A program with no such counter at all (one from before the
phases) reports nothing; once it has them, a counter this reader is asked
for and does not find is an error, never 0: a renamed phase must fail
loudly."""

PREFIX = "phase_us_"


def total_us(rec, phases):
    """Sum of the window's ``phase_us_<p>``, or None where the program
    has no phase counters."""
    have = sorted(k for k in rec.counters if k.startswith(PREFIX))
    if not have:
        return None
    missing = [p for p in phases if PREFIX + p not in rec.counters]
    if missing:
        raise KeyError(f"no counter {PREFIX}<phase> for {missing}; the "
                       f"program has {have}")
    return sum(rec.counters[PREFIX + p] for p in phases)


def read(rec, phases, minus=()):
    plus, less = total_us(rec, phases), total_us(rec, minus)
    if plus is None or not rec.requests:
        return None
    return (plus - less) / 1e3 / len(rec.requests)
