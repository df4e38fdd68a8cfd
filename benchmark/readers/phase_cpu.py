"""Per request, the CPU time the program's threads spent inside its
metered phases, or, with ``off_cpu``, the wall time of the named phases
less their CPU time: the time pure host work was under way and its
thread was not on a CPU.

``pinot_tpu/utils/spans.phase`` reads the thread's CPU clock in a drawn
share of its crossings: those add their self CPU to ``phase_cpu_us_<p>``
and their wall time to ``phase_cpu_wall_us_<p>``, the others 0 to both.
A phase's CPU time in the window is then its whole wall time
``phase_us_<p>`` times the CPU share of the wall time it was read over.
The rules are ``phase_ms``'s: a program with no ``phase_cpu_us_*``
counter at all (one from before them) reports nothing; a named phase the
window never crossed (no ``phase_n_<p>``), or never read, counts 0; one it
crossed without its CPU counters beside it is an error, never 0."""
from benchmark.readers.phase_ms import PREFIX as WALL

CPU = "phase_cpu_us_"
READ_OVER = "phase_cpu_wall_us_"
CROSSINGS = "phase_n_"


def cpu_us(c, p):
    """The phase's CPU microseconds in the window, from its read share."""
    if CPU + p not in c or READ_OVER + p not in c:
        raise KeyError(f"phase {p!r} was crossed, and the program has no "
                       f"{CPU}{p} or {READ_OVER}{p}")
    over = c[READ_OVER + p]
    return c[WALL + p] * c[CPU + p] / over if over else 0.0


def read(rec, phases=None, off_cpu=False):
    c = rec.counters
    if not rec.requests or not any(k.startswith(CPU) for k in c):
        return None
    if phases is None:
        phases = {k[len(pre):] for pre in (CPU, READ_OVER) for k in c
                  if k.startswith(pre)}
    crossed = [p for p in phases if CROSSINGS + p in c]
    cpu = sum(cpu_us(c, p) for p in crossed)
    us = sum(c[WALL + p] for p in crossed) - cpu if off_cpu else cpu
    return us / 1e3 / len(rec.requests)
