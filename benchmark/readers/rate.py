"""Right answers received inside the window per second of the window."""
from benchmark import stats


def read(rec):
    return stats.rate([r.done for r in rec.answered()], rec.t0, rec.seconds)
