"""Per query, the client's latency less the time an operation ran on the
device inside the request's own interval: broker, wire, plan, transfer,
reduce. Only where one query runs at a time, or another's kernel would be
taken off this one's host path."""
from benchmark.trace.reduce import overlap


def read(rec):
    t = rec.trace
    if t is None or not t.requests:
        return None
    outside = [(r.end - r.start) - sum(overlap(d.busy, r.start, r.end)
                                       for d in t.devices) / len(t.devices)
               for r in t.requests if r.end <= t.window[1]]
    return 1e3 * sum(outside) / len(outside) if outside else None
