"""Share of the HBM roofline: the least time the chips could take to
read the queries' logical bytes (bytes / peak bytes per second, summed
over the devices used) over the time operations ran on the device inside
those requests. Bound by bytes, not FLOPs: a scan does a handful of
integer operations per 4-byte value. Requests cut by the window's end
are left out on both sides of the ratio."""
from benchmark import catalog
from benchmark.trace.reduce import overlap


def read(rec):
    t = rec.trace
    if t is None:
        return None
    whole = [r for r in t.requests if r.end <= t.window[1]]
    bytes_ = sum(rec.logical_bytes(rec.statements[r.stats["key"]].shape)
                 for r in whole)
    device_s = sum(overlap(d.busy, r.start, r.end)
                   for r in whole for d in t.devices) / len(t.devices)
    if not bytes_ or not device_s:
        return None
    peak = catalog.peak(rec.device_kind)["hbm_bytes_per_s"] * len(t.devices)
    return 100.0 * (bytes_ / peak) / device_s
