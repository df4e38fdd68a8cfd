"""The selectivity x group-space grid as a CPU digest sweep.

The hardware gate for chip-only lowerings is ``chip_smoke.py`` at the
repo root (run on the chip through the chip tool); it imports the same
``tpu_hw_script`` library. Nothing here starts a device process.
"""


def test_selectivity_grid_cpu_digest():
    """The q2.x/q3.x/q4.3-shaped selectivity x group-space grid runs on
    EVERY backend asserting digest-exactness vs the numpy oracle — on
    CPU a pure correctness sweep, including the empty-result and
    all-rows-match edges."""
    import tpu_hw_script

    tpu_hw_script.run_selectivity_grid(1 << 16)
