"""Charge the filter program's device time to the program's named scopes.

The program names its layers with ``jax.named_scope`` (``filter.resample``,
``store.refcount``, ...).  XLA copies the scope path into the ``op_name``
metadata of every instruction it lowers to, fusions included, but the
device ops of a TPU trace carry only their instruction's name.  So the
join goes through the program's optimized HLO: an op's name (the first
token of :func:`chipbench.trace.short_name`, ``%`` dropped) is looked up
among the instructions of ``jax.jit(pf.run)`` compiled for the same
backend, and its ``op_name`` gives the scope path.  An op counts towards
the innermost declared scope on that path; a scope's subtree holds it and
every scope nested inside it.

Only ops inside a call of the filter program (``jit_run``) count, and
control-flow ops that enclose others are left out, as in the trace's own
breakdown.  If the join finds under :data:`MIN_JOINED` of that time, the
HLO is not the traced program's and nothing is read.  A program without
the scopes (no declared name anywhere in its HLO) reads nothing either.

    python3 chipbench/scopes.py --workload rbpf.paper [--trace <dir>] [--top 10]

prints every scope's milliseconds per generation in the cell's last trace
(``chipbench_out/traces/<cell>`` by default) and the largest ops with
their scopes.  Run it where the trace was recorded: the HLO is compiled
for the local backend.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import re
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]

# Declared in the program (smc/filters.py, core/store.py, core/pool.py).
SCOPES = (
    "filter.resample",
    "filter.propagate",
    "store.refcount",
    "store.append",
    "store.count",
    "pool.alloc",
    "pool.free_push",
)
PROGRAM = r"jit_run"
MIN_JOINED = 0.99
# Ops that enclose the ops of other computations on the same trace line.
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
# The opcode is the first lower-case word before a "(" after the shape
# (layout tiles such as ``T(8,128)`` are upper-case).
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> ``(opcode, op_name)`` (``op_name`` "" where the
    instruction has none)."""
    out: Dict[str, Tuple[str, str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            code = _OPCODE.search(line, m.end() - 1)
            meta = _OP_NAME.search(line)
            out[m.group(1)] = (code.group(1) if code else "",
                               meta.group(1) if meta else "")
    return out


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The declared scopes on an ``op_name``, outermost first."""
    return tuple(part for part in op_name.split("/") if part in SCOPES)


def instruction(event_name: str) -> str:
    """A trace op's HLO instruction name: ``%fusion.18 = s32[...] ...`` ->
    ``fusion.18`` on a TPU, the event name itself on the CPU."""
    from chipbench.trace import short_name

    return short_name(event_name).split(" ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class Split:
    """Device time of the program's ops, by scope path."""

    calls: int
    op_s: float  # every op inside the program's calls
    by_path: Dict[Tuple[str, ...], float]  # joined ops only; () = unscoped
    ops: Dict[str, float]  # instruction -> seconds, joined ops only

    def subtree(self, scope: str) -> float:
        return sum(s for p, s in self.by_path.items() if scope in p)

    def exclusive(self, scope: str) -> float:
        return sum(s for p, s in self.by_path.items() if p and p[-1] == scope)

    @property
    def unscoped(self) -> float:
        return self.by_path.get((), 0.0)

    @property
    def joined_s(self) -> float:
        return sum(self.by_path.values())


def split(trace, names: Dict[str, Tuple[str, str]]) -> Optional[Split]:
    """Join a reduced trace's program ops with ``names`` (:func:`op_names`).

    ``None`` when no call of the program lies in the window, when the
    program declares none of :data:`SCOPES`, or when under
    :data:`MIN_JOINED` of the op time is found in ``names``."""
    calls = trace.matching(PROGRAM, where="modules")
    if not calls or not any(scope_path(o) for _, o in names.values()):
        return None
    module = {c.stat("hlo_module") for c in calls} - {None}
    per_dev: Dict[int, List[Tuple[int, int]]] = defaultdict(list)
    for c in calls:
        per_dev[c.device].append((c.start, c.end))
    for iv in per_dev.values():
        iv.sort()
    op_s = 0.0
    by_path: Dict[Tuple[str, ...], float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    for e in trace.ops:
        name = instruction(e.name)
        if names.get(name, ("",))[0] in CONTAINER_OPCODES:
            continue
        if e.stat("hlo_module") is not None and e.stat("hlo_module") not in module:
            continue
        iv = per_dev.get(e.device, [])
        i = bisect.bisect_right(iv, (e.start, float("inf"))) - 1
        if i < 0 or e.end > iv[i][1]:
            continue
        op_s += e.seconds
        if name in names:
            by_path[scope_path(names[name][1])] += e.seconds
            ops[name] += e.seconds
    if op_s <= 0 or sum(by_path.values()) < MIN_JOINED * op_s:
        return None
    return Split(len(calls), op_s, dict(by_path), dict(ops))


# -- the filter program's HLO, once per process ------------------------------

_HLO: Dict[str, Dict[str, Tuple[str, str]]] = {}
_LAST: List[tuple] = []  # [(trace, split)]


def _log(msg: str) -> None:
    print(f"chipbench: scopes: {msg}", file=sys.stderr, flush=True)


def filter_hlo(cell) -> str:
    """The optimized HLO text of ``jax.jit(pf.run)`` for the cell's filter,
    lowered on the shapes and dtypes of the driver's own arguments."""
    import jax

    from chipbench.bench import Bench

    drv = Bench(ROOT).driver(cell.spec["driver"])
    conf = cell.config["filter"]
    pf, params = drv._filter(conf)
    steps = int(conf["n_steps"])
    key = jax.eval_shape(lambda: drv.traffic_lib.jax_key(0, drv.KEY_STREAM))
    ys = jax.eval_shape(lambda: drv.ref_lib.simulate(
        drv.traffic_lib.jax_key(0, drv.OBS_STREAM), steps))

    def compiled_text() -> str:
        return jax.jit(pf.run).lower(key, params, ys).compile().as_text() or ""

    text = compiled_text()
    if not _OP_NAME.search(text):
        # An executable loaded from the persistent cache may carry no
        # HLO text; compiling afresh gives the same instruction names.
        from jax.experimental.compilation_cache import compilation_cache

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()
        try:
            text = compiled_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
        _log("the cached executable gave no HLO text; compiled afresh")
    return text


def names_for(cell) -> Dict[str, Tuple[str, str]]:
    key = json.dumps(cell.config["filter"], sort_keys=True)
    if key not in _HLO:
        t0 = time.perf_counter()
        _HLO[key] = op_names(filter_hlo(cell))
        _log(f"filter program lowered and compiled for its HLO in "
             f"{time.perf_counter() - t0:.3f} s")
    return _HLO[key]


def for_run(trace, cell) -> Optional[Split]:
    """The split of ``trace``, computed once for all the readers."""
    if not _LAST or _LAST[0][0] is not trace:
        _LAST[:] = [(trace, split(trace, names_for(cell)))]
    return _LAST[0][1]


def ms_per_generation(run, trace, cell, scope: str) -> Optional[float]:
    """Device time of ``scope``'s subtree per call and generation, in ms."""
    s = for_run(trace, cell)
    if s is None:
        return None
    return 1e3 * s.subtree(scope) / (s.calls * run["n_steps"])


# -- the table ------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", default=None,
                    help="trace directory (default: chipbench_out/traces/<cell>)")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    from chipbench import run as run_lib
    from chipbench import trace as trace_lib
    from chipbench.bench import Bench

    # The traced run's own cache: the executable it ran, with its metadata
    # (the cache key leaves metadata out, so another cache could hold the
    # same program under other scopes).
    run_lib._enable_cache(ROOT)

    cell = Bench(ROOT).cell(args.workload)
    reduced = trace_lib.load(Path(args.trace) if args.trace
                             else ROOT / "chipbench_out" / "traces" / cell.name)
    names = names_for(cell)
    s = split(reduced, names)
    if s is None:
        print("no split: no program call in the window, no declared scope, or under "
              f"{MIN_JOINED:.0%} of the op time joined")
        return 1
    per = 1e3 / (s.calls * int(cell.config["filter"]["n_steps"]))
    print(f"{s.calls} call(s) of {PROGRAM}; op time {s.op_s:.6f} s; joined "
          f"{100 * s.joined_s / s.op_s:.3f}%")
    print(f"{'scope':<20} {'own ms/gen':>12} {'subtree ms/gen':>15}")
    for scope in SCOPES:
        own, sub = per * s.exclusive(scope), per * s.subtree(scope)
        print(f"{scope:<20} {own:12.4f} {sub:15.4f}")
    print(f"{'(unscoped)':<20} {per * s.unscoped:12.4f}")
    print(f"{'(not joined)':<20} {per * (s.op_s - s.joined_s):12.4f}")
    print(f"\ntop {args.top} ops, ms per generation:")
    for name, secs in sorted(s.ops.items(), key=lambda kv: -kv[1])[:args.top]:
        code, op_name = names[name]
        path = scope_path(op_name)
        print(f"{name:<28} {code:<12} {per * secs:10.4f}  "
              f"{'/'.join(path) or '(unscoped)':<40} {op_name}")
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
