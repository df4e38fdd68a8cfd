"""How much one of the counters moved across the window."""


def read(rec, counter):
    return float(rec.counters.get(counter, 0))
