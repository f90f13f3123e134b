"""Which tree the yardstick's tests read.

The tests that glob configurations, mixes and metric files take their root
from here, not from where they lie: the repo's own, or the tree that
``BENCHMARK_TREE`` names — a copy of ``benchmark/`` with a second family's
files laid into it (``test_benchmark_second_family.py``), whose
``BENCHMARK.json``, ``benchmark/configs``, ``benchmark/traffic`` and metric
files they then hold to the same assertions. The tree's ``benchmark``
package is the one imported, so import this module before ``benchmark``.
"""

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = Path(os.environ.get("BENCHMARK_TREE") or HERE.parents[1])
sys.path.insert(0, str(REPO))

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def load_dir(folder: str) -> dict:
    """``{stem: parsed file}`` of ``benchmark/<folder>/*.json``."""
    return {p.stem: json.loads(p.read_text()) for p in sorted((REPO / "benchmark" / folder).glob("*.json"))}
