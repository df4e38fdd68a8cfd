"""Share of the HBM roofline over the requests of some statements only:
``scan_roofline``'s ratio (the least time the chips could take to read
the requests' logical bytes, over the time operations ran on the device
inside them) restricted to the statements named, so that the full-scan
group-by's share is read apart from the cheaper statements of its cell.
It counts the bytes a statement reads, so it holds whatever kernel
answers it."""
from benchmark import catalog
from benchmark.trace.reduce import overlap


def read(rec, statements):
    t = rec.trace
    if t is None:
        return None
    whole = [r for r in t.requests if r.end <= t.window[1]
             and r.stats["key"] in statements]
    bytes_ = sum(rec.logical_bytes(rec.statements[r.stats["key"]].shape)
                 for r in whole)
    device_s = sum(overlap(d.busy, r.start, r.end)
                   for r in whole for d in t.devices) / len(t.devices)
    if not bytes_ or not device_s:
        return None
    peak = catalog.peak(rec.device_kind)["hbm_bytes_per_s"] * len(t.devices)
    return 100.0 * (bytes_ / peak) / device_s
