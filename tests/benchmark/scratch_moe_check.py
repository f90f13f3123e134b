"""``benchmark/check.py`` for a configuration of the scratch routed family,
which lives beside the tests and not in ``benchmark/families``:

    python3 tests/benchmark/scratch_moe_check.py --config tests/benchmark/scratch-moe-a3b-l2.json --seed 1

registers the family and its reference under the names ``load_family`` and
the family look for, as the seam test's fixture does, and hands over to
``check.main`` (same arguments, same output). How PERF.md's table of the
routed check at published expert widths was made, on the chip.
"""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import scratch_moe_family  # noqa: E402

from benchmark import check  # noqa: E402

sys.modules["benchmark.families.scratch_moe"] = scratch_moe_family

if __name__ == "__main__":
    sys.exit(check.main())
