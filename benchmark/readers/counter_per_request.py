"""How far one of the program's work counters moved across the window,
per request. It keeps the rule of ``phase_ms``: nothing from a program
without the phase counters, an error for a counter missing beside them."""
from benchmark.readers.phase_ms import PREFIX


def read(rec, counter):
    if not any(k.startswith(PREFIX) for k in rec.counters) \
            or not rec.requests:
        return None
    if counter not in rec.counters:
        raise KeyError(f"the program has no counter {counter!r}")
    return rec.counters[counter] / len(rec.requests)
