"""Read a profiler trace (``*.xplane.pb``) into plain data.

``load`` keeps what the reduction needs and nothing else: the device
planes' lines and, from the host plane, the benchmark's own
``TraceAnnotation`` spans (names starting ``bench_``). The result is
JSON-able, which is how the recorded trace the tests use was written.

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, stats]]}]}]}
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, Dict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench_"


def find(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` run left in ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


def load(path: str) -> Dict[str, Any]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if is_device:
                    events.append([ev.name, ev.start_ns, ev.duration_ns, {}])
                elif ev.name.startswith(SPAN_PREFIX):
                    events.append([ev.name, ev.start_ns, ev.duration_ns,
                                   {k: v for k, v in ev.stats}])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)
