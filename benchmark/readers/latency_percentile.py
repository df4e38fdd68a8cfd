"""A percentile of client-side latency over every request of the window
(a failed one counts the time until the client gave up), optionally of
one flight's statements only."""
from benchmark import stats


def read(rec, q, flight=None):
    ms = [r.latency_ms for r in rec.requests if flight is None
          or rec.statements[r.key].shape["flight"] == flight]
    return stats.percentile(ms, q) if ms else None
