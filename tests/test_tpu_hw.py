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


def test_whole_numbers_and_dates_check_passes_on_the_cpu():
    """The chip's check of ROUND, FLOOR and the civil date fields holds
    on the CPU backend too, and names each function it checked."""
    import tpu_hw_script

    out = {"checks": []}
    tpu_hw_script.check_whole_numbers_and_dates(out)
    assert out["checks"] == [
        "device:round_beside_ties", "device:floor_beside_ties",
        "device:year_of_int64_ms", "device:month_of_int64_ms",
        "device:day_of_int64_ms", "device:quarter_of_int64_ms"]
