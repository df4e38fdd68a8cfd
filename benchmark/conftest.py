"""Four virtual CPU devices for the benchmark's own tests, set before any
backend starts: the cell ``ssb4.suite_c1`` spreads its table over the
first four devices JAX finds (``entries/served_http_mesh.py``). The
one-chip cells keep using the first."""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
