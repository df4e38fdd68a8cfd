"""A sum of the program's work counters over the window, per request, or
(``over``) as a share of another sum. It keeps the rule of ``phase_ms``:
nothing from a program without the phase counters. A counter the program
never moved is absent from its registry: among ``counters`` that reads 0
only if another of them is there or ``absent_is_zero`` says the counter
counts faults (one that stays absent is the good reading); a program
that has none of them reports nothing."""
from benchmark.readers.phase_ms import PREFIX


def read(rec, counters, over=None, scale=1.0, absent_is_zero=False):
    if not any(k.startswith(PREFIX) for k in rec.counters) \
            or not rec.requests:
        return None
    if not absent_is_zero and not any(c in rec.counters for c in counters
                                      + list(over or ())):
        return None
    total = sum(rec.counters.get(c, 0) for c in counters)
    if over is None:
        return scale * total / len(rec.requests)
    base = sum(rec.counters.get(c, 0) for c in over)
    return scale * total / base if base else None
