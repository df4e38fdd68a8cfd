"""Per request, the device time spent in collective operations: the self
time of the trace's ``XLA Ops`` events whose operation is an all-reduce,
all-gather, all-to-all, reduce-scatter or collective-permute (the
``-start`` and ``-done`` halves of an asynchronous one included),
averaged over the devices, in ms. A trace with no such operation (one
chip, or a program without a mesh path) reports nothing."""
import re

COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute)\b")


def collective_seconds(trace) -> float:
    """Self seconds of the collective operations, averaged over devices."""
    total = sum(s for d in trace.devices for name, s in d.op_self_s.items()
                if COLLECTIVE.match(name))
    return total / len(trace.devices)


def read(rec):
    t = rec.trace
    if t is None or not t.requests:
        return None
    secs = collective_seconds(t)
    return 1e3 * secs / len(t.requests) if secs else None
