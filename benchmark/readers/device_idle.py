"""Share of the traced window in which no operation ran on the device,
the mean over the devices used."""


def read(rec):
    t = rec.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
