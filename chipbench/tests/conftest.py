"""The chip benchmark's own tests (CPU): ``python -m pytest chipbench/tests``.

They sit outside the repository's tier-1 ``testpaths``.  Runs of a cell
here use tiny shapes, no chip (``require_chip=False``) and a benchmark
root built in a temporary directory, so they never touch the real
``BENCHMARK.json`` cells' sizes.
"""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SHARED = ("drivers", "metrics", "reference", "counts")


def tiny_root(tmp: Path) -> Path:
    """A benchmark root whose cell is the real one at a tiny size."""
    root = tmp / "bench"
    (root / "chipbench").mkdir(parents=True)
    src = REPO / "chipbench"
    for d in SHARED:
        shutil.copytree(src / d, root / "chipbench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(src / "peaks.json", root / "chipbench" / "peaks.json")
    for d in ("configs", "traffic", "workloads"):
        (root / "chipbench" / d).mkdir()

    def load(rel):
        return json.loads((src / rel).read_text())

    def dump(rel, obj):
        (root / "chipbench" / rel).write_text(json.dumps(obj))

    rb = load("configs/rbpf.json")
    rb["filter"].update(n_particles=64, n_steps=24)
    dump("configs/rbpf.json", rb)
    dump("traffic/paper.json", load("traffic/paper.json"))
    cell = load("workloads/rbpf.paper.json")
    cell["trace"] = {"start_share": 0.2, "seconds": 1.0}
    dump("workloads/rbpf.paper.json", cell)
    (root / "BENCHMARK.json").write_text(json.dumps(TINY_BENCHMARK))
    return root


# The tiny root's own BENCHMARK.json: the cell with the end-to-end
# metrics its driver reports.
TINY_BENCHMARK = {
    "workloads": [
        {"name": "rbpf.paper", "config": "rbpf", "traffic": "paper", "chips": 1},
    ],
    "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "particle_steps_per_s", "unit": "steps/s", "workloads": ["rbpf.paper"]},
    ],
    "per_layer": [],
}


@pytest.fixture(scope="session")
def tiny(tmp_path_factory) -> Path:
    return tiny_root(tmp_path_factory.mktemp("chipbench"))
