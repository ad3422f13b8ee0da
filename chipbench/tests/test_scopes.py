"""The per-scope readers on a CPU trace of the small filter.

A traced run of the tiny cell records the trace in ``tmp_path``; the five
readers read it through :mod:`chipbench.scopes`, whose join must account
for the program's op time, and must refuse to guess when the HLO map it
joins with is cut in half.
"""

import json
import math

import pytest

from chipbench import run as run_lib
from chipbench import scopes
from chipbench import trace as trace_lib
from chipbench.bench import Bench
from chipbench.tests.conftest import REPO, tiny_root

READERS = ("resample_ms.filter", "refcount_ms.filter", "append_ms.filter",
           "propagate_ms.filter", "unscoped_share.filter")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("scopes"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"] = [m for m in json.loads((REPO / "BENCHMARK.json").read_text())
                         ["per_layer"] if m["name"] in READERS]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell_file = root / "chipbench" / "workloads" / "rbpf.paper.json"
    cell = json.loads(cell_file.read_text())
    cell["trace"] = {"start_share": 0.2, "seconds": 0.25}
    cell_file.write_text(json.dumps(cell))
    res = run_lib.run_cell("rbpf.paper", 3000000013, 1.0, True, root=root,
                           require_chip=False, log=lambda s: None)
    reduced = trace_lib.load(root / run_lib.OUT_DIR_NAME / "traces" / "rbpf.paper")
    return res, reduced, Bench(root).cell("rbpf.paper")


def test_readers_return_numbers(traced):
    res, _, _ = traced
    assert res["correct"], res["checks"]
    values = {name: res["metrics"][name]["value"] for name in READERS}
    assert all(math.isfinite(v) for v in values.values()), values
    for name in READERS[:4]:
        assert values[name] > 0, values
    assert 0 <= values["unscoped_share.filter"] < 100, values


def test_scopes_and_unscoped_add_up_to_the_program(traced):
    _, reduced, cell = traced
    s = scopes.split(reduced, scopes.names_for(cell))
    assert s is not None and s.calls >= 1
    parts = sum(s.exclusive(scope) for scope in scopes.SCOPES) + s.unscoped
    assert parts == pytest.approx(s.op_s, rel=0.01)
    # A subtree holds its own time and every scope nested inside it.
    assert s.subtree("filter.resample") >= s.subtree("store.refcount") > 0
    assert s.subtree("store.append") >= s.subtree("pool.alloc") > 0


def test_half_the_hlo_map_withheld_reads_nothing(traced):
    _, reduced, cell = traced
    names = scopes.names_for(cell)
    half = dict(sorted(names.items())[::2])
    assert scopes.split(reduced, half) is None


def test_a_program_without_scopes_reads_nothing(traced):
    _, reduced, cell = traced
    bare = {k: (code, "") for k, (code, _) in scopes.names_for(cell).items()}
    assert scopes.split(reduced, bare) is None


def test_an_executable_without_text_is_compiled_afresh(traced, monkeypatch):
    import jax

    _, _, cell = traced
    real, texts = jax.stages.Compiled.as_text, []

    def as_text(self):
        texts.append(real(self) if texts else "")
        return texts[-1]

    monkeypatch.setattr(jax.stages.Compiled, "as_text", as_text)
    assert "store.refcount" in scopes.filter_hlo(cell)
    assert len(texts) == 2
