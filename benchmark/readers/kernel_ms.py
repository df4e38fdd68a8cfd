"""Device-operation (self) time in the traced window per request, in ms,
averaged over the devices."""


def read(rec):
    t = rec.trace
    if t is None or not t.requests:
        return None
    secs = t.op_seconds()
    return 1e3 * secs / len(t.requests) if secs else None
