"""Device program executions in the traced window per request."""


def read(rec):
    t = rec.trace
    if t is None or not t.requests:
        return None
    n = sum(len(d.launches) for d in t.devices) / len(t.devices)
    return n / len(t.requests) if n else None
