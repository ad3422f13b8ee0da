"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` and its files under
``chipbench/`` (:mod:`chipbench.bench`).  A run:

1. refuses to start unless JAX finds a TPU and as many chips as the cell
   asks for (exit code 1, no result line);
2. keeps JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
3. lets the cell's driver make its weights and inputs from ``--seed``,
   warm every shape the cell uses, then measure for ``--seconds``;
4. checks what the timed path produced against the plain reference under
   ``chipbench/reference/`` and prints each compared number beside its
   limit, on standard error and as the last key of the result line;
5. prints the result as one JSON object on the last line of standard
   output: with ``--trace 0`` the cell's end-to-end metrics, with
   ``--trace 1`` its per-layer metrics, read from a profiler trace of part
   of the window and from the harness's own clocks and counters.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # the start of set-up

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR_NAME = ".jax_cache"
OUT_DIR_NAME = "chipbench_out"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads as they happen."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_loads = 0

    def on_duration(self, event: str, secs: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
            self.compile_s += secs

    def on_event(self, event: str, **_) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.cache_loads += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "cache_loads": self.cache_loads,
        }


class Context:
    """What a driver gets: the cell, the run's arguments, and the window
    and trace controls.  Drivers call :meth:`start_window` when set-up is
    done and :meth:`end_window` when the window closes."""

    def __init__(self, cell, *, seed: int, seconds: float, trace: bool,
                 t_start: float, counter: CompileCounter, out_dir: Path,
                 log: Callable[[str], None], control: bool = False):
        self.cell = cell
        # Calibration and tests only: the driver puts the control (the
        # reference one precision lower) in the program's place in the
        # comparison, so the limits judge it and ``correct`` reads false.
        self.control = control
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.counter = counter
        self.out_dir = out_dir
        self.log = log
        self.setup_s: Optional[float] = None
        self.window_t0: Optional[float] = None
        self.window_s: Optional[float] = None
        self.window_compiles: Dict[str, float] = {}
        self.trace_path: Optional[Path] = None
        self._compiles0: Dict[str, float] = {}
        self._tracing = False

    # -- the measured window ----------------------------------------------
    def start_window(self) -> float:
        import jax

        jax.effects_barrier()
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self.window_t0 = now
        self._compiles0 = self.counter.snapshot()
        self.log(f"set-up done in {self.setup_s:.3f} s "
                 f"({self.counter.compiles} compiles, {self.counter.cache_loads} "
                 f"cache loads so far); window opens")
        return now

    def end_window(self, t_end: float) -> None:
        self.window_s = t_end - self.window_t0
        snap = self.counter.snapshot()
        self.window_compiles = {k: snap[k] - self._compiles0[k] for k in snap}
        if self._tracing:
            self.stop_trace()
        print(f"chipbench: compiles inside the window: "
              f"{int(self.window_compiles['compiles'])} "
              f"({self.window_compiles['compile_s']:.3f} s), persistent-cache "
              f"loads inside the window: {int(self.window_compiles['cache_loads'])}",
              flush=True)

    # -- the traced part of the window (``--trace 1`` only) ---------------
    def trace_due(self, now: float) -> bool:
        """Start tracing once this share of the window has passed; stop
        after ``trace_seconds`` (both from the cell's file)."""
        if not self.trace:
            return False
        spec = self.cell.spec.get("trace", {})
        elapsed = now - self.window_t0
        begin = float(spec.get("start_share", 0.3)) * self.seconds
        if not self._tracing and self.trace_path is None and elapsed >= begin:
            self.start_trace()
        elif self._tracing and elapsed >= begin + float(spec.get("seconds", 5.0)):
            self.stop_trace()
        return self._tracing

    def start_trace(self) -> None:
        import shutil

        import jax

        d = self.out_dir / "traces" / self.cell.name
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True, exist_ok=True)
        self.trace_path = d
        # Host spans come from TraceMe annotations (the harness's and
        # JAX's own); the Python tracer would time every Python call.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(d), profiler_options=opts)
        self._window_span = jax.profiler.TraceAnnotation("chipbench.window")
        self._window_span.__enter__()
        self._tracing = True

    def stop_trace(self) -> None:
        import jax

        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False


def _check_chip(chips: int, require_chip: bool) -> List[Any]:
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devices)}")
    return devices


def _enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout.

    Set before JAX is imported, so the program's own
    ``repro.compile_cache.enable()`` takes the same directory."""
    d = str(root / CACHE_DIR_NAME)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    return d


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_chip: bool = True,
             t_start: Optional[float] = None,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True),
             control: bool = False,
             ) -> Dict[str, Any]:
    """One run of one cell; returns the result object (see module doc).

    Raises :class:`NoChip` before any work when the chip is missing."""
    t_start = T_PROCESS if t_start is None else t_start
    root = Path(root)
    # The persistent cache is for the chip; CPU runs (the benchmark's own
    # tests) compile afresh.
    cache_dir = _enable_cache(root) if require_chip else None
    from chipbench.bench import Bench

    bench = Bench(root)
    cell = bench.cell(workload)

    import jax

    devices = _check_chip(cell.chips, require_chip)
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        from repro import compile_cache

        log(f"compile cache: {compile_cache.enable()}")
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; jax {jax.__version__}; "
        f"cell {cell.name} (config {cell.config_name}, traffic {cell.traffic_name}); "
        f"seed {seed}; {seconds} s; trace {int(trace)}")
    ctx = Context(cell, seed=seed, seconds=seconds, trace=trace, t_start=t_start,
                  counter=counter, out_dir=root / OUT_DIR_NAME, log=log, control=control)
    driver = bench.driver(cell.spec["driver"])
    rec = driver.run(ctx, devices)
    gc.collect()
    rec["setup_s"] = ctx.setup_s
    rec["window_s"] = ctx.window_s
    rec["window_compiles"] = ctx.window_compiles

    device: Dict[str, Any] = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": rec.pop("memory_peak_bytes"),
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    reduced = None
    if trace:
        from chipbench import trace as trace_lib

        if ctx.trace_path is None:
            raise RuntimeError("the window ended before its traced part began")
        reduced = trace_lib.load(ctx.trace_path)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    peaks = bench.peaks(dev.device_kind) if dev.platform == "tpu" else None
    for m in wanted:
        value = bench.metric(m["name"]).read(rec, reduced, cell=cell, peaks=peaks)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = rec["checks"]
    result: Dict[str, Any] = {
        "correct": bool(checks) and all(c["ok"] for c in checks),
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    for c in checks:
        log(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r}): "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; nothing run", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
