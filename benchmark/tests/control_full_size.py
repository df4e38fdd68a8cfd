"""The control, at the cells' own size, through the harness itself: the
plain reference put in the program's place under the timed path with every
sum kept in float32 — the precision below the exact 64-bit integers the
configuration states, and the step a later PR on a chip without native
int64 would be tempted by. ``run_cell`` has to say ``correct`` false for
it (that it says true with the sums left exact is tested at a tiny size in
``test_cells_cpu.py``). Run it on the machine with the chip, so that the
size and the harness's checks are the cell's:

    python3 benchmark/tests/control_full_size.py <seed> [<seed> ...]
"""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import catalog as cat  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.tests.inplace import ReferenceInPlace  # noqa: E402

WINDOW_S = 0.05     # answers come from memory: thousands a second


def main(seeds) -> int:
    catalog = cat.Catalog()
    ok = True
    for seed in seeds:
        for cell in catalog.cells:
            res = run.run_cell(
                cell, seed, WINDOW_S, False, catalog=catalog,
                wrap_system=lambda system, segs: ReferenceInPlace(
                    system, segs, round_to=np.float32))
            print(f"seed {seed} cell {cell} control float32: correct "
                  f"{res['correct']} compared "
                  f"{json.dumps(res['compared'])}", flush=True)
            ok = ok and not res["correct"]
    print("the control fails every cell on every seed:", ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [1, 2, 3]))
