"""Per request, what the client waited beyond the broker's own handling:
the mean client-side latency of the window's requests less the broker's
``broker_query`` phase (body parsed to response written). Connection,
request and response transfer, the client's JSON parse."""
from benchmark.readers.phase_ms import total_us


def read(rec):
    us = total_us(rec, ["broker_query"])
    if us is None or not rec.requests:
        return None
    mean_ms = sum(r.latency_ms for r in rec.requests) / len(rec.requests)
    return mean_ms - us / 1e3 / len(rec.requests)
