"""What the serving readers take from a trace: the scheduler ticks the
trace holds, and the device work inside them.

The serve driver wraps each tick in a ``chipbench.serve.tick`` host span
and marks the ticks that ran while the profiler was on (``traced`` in
its tick records); those are the spans inside the traced window.  A
reader that finds a different number of spans than the run recorded
reads nothing.
"""

from __future__ import annotations

import bisect
import functools
import json
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench.scopes import _INSTRUCTION, MIN_JOINED
from chipbench.trace import short_name, union_length

TICK_SPAN = "chipbench.serve.tick"


def spans(run, trace) -> Optional[List[Tuple[int, int]]]:
    """The traced ticks' host spans (ns, the trace's clock), or ``None``
    when they do not match the run's traced ticks one for one."""
    lo, hi = trace.window
    found = sorted((h.start, h.end) for h in trace.host
                   if h.name == TICK_SPAN and lo <= h.start and h.end <= hi)
    traced = sum(1 for t in run["ticks"] if t["traced"])
    return found if found and len(found) == traced else None


def busy_s(trace, ticks: List[Tuple[int, int]]) -> float:
    """Device time inside the ticks: the union of operation intervals
    clipped to them, averaged over the devices."""
    per = defaultdict(list)
    for e in trace.ops:
        for s, t in ticks:
            a, b = max(e.start, s), min(e.end, t)
            if b > a:
                per[e.device].append((a, b))
    return sum(union_length(iv) for iv in per.values()) / max(trace.n_devices, 1) / 1e9


def programs(trace, ticks: List[Tuple[int, int]]) -> list:
    """Device program executions (the trace's module events) that start
    inside the ticks."""
    return [m for m in trace.modules if any(s <= m.start < t for s, t in ticks)]


# -- the decode program ---------------------------------------------------------

_DECODE: Dict[str, Tuple[str, frozenset]] = {}


def _key(text: str) -> str:
    """An instruction's name and shape (``copy.148 bf16[513,32,2,16,2048]``)
    from its HLO line or a TPU op's name; a CPU op's name is the bare
    instruction name, and so is its key."""
    return short_name(re.sub(r"^\s*(ROOT\s+)?%?", "", text))


def decode_instructions(cell) -> Tuple[str, frozenset]:
    """The module name and the join keys of every instruction of the
    engine's decode step compiled for the cell's shapes on this backend
    (once per process)."""
    key = json.dumps([cell.config["model"], cell.config["serve"]], sort_keys=True)
    if key not in _DECODE:
        text = decode_hlo(cell)
        keys = {_key(line) for line in text.splitlines() if _INSTRUCTION.match(line)}
        module = re.match(r"HloModule ([^\s,]+)", text)
        _DECODE[key] = (module.group(1) if module else "",
                        frozenset(keys | {k.split(" ", 1)[0] for k in keys}))
    return _DECODE[key]


def decode_hlo(cell) -> str:
    """The optimized HLO of ``ServeEngine``'s decode step at the cell's
    sizes: the engine's own function, jitted as the engine jits it and
    lowered on the shapes the driver gives it."""
    import jax
    import jax.numpy as jnp

    from chipbench.drivers.serve import _engine_config, config_module
    from repro.serving import engine as engine_lib
    from repro.serving import kv_cache as kvc

    model, serve = cell.config["model"], cell.config["serve"]
    cfg, ccfg = _engine_config(model, serve)
    make = config_module("reference", cell).make_params
    params = jax.eval_shape(lambda k: make(model, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: kvc.create(ccfg))
    rows = ccfg.max_seqs
    step = jax.jit(functools.partial(engine_lib._decode_step, cfg, ccfg))
    args = (params, cache, jax.ShapeDtypeStruct((rows, 1), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.bool_))
    text = step.lower(*args).compile().as_text() or ""
    if not text:
        # An executable loaded from the persistent cache may carry no HLO
        # text; compiling afresh gives the same instructions.
        from jax.experimental.compilation_cache import compilation_cache

        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            text = jax.jit(functools.partial(engine_lib._decode_step, cfg, ccfg)
                           ).lower(*args).compile().as_text() or ""
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()
    return text


def decode_calls(trace, cell, ticks: List[Tuple[int, int]]) -> list:
    """The decode step's executions inside the ticks.

    The trace names the engine's jitted ``functools.partial`` programs
    alike (``jit__unknown(<id>)`` on a TPU, ``jit__unknown`` on the CPU),
    so a program of the decode step's module name counts as the decode
    step when at least :data:`MIN_JOINED` of the op time inside its
    executions is made of the decode step's instructions."""
    module, keys = decode_instructions(cell)
    calls = [m for m in programs(trace, ticks)
             if m.name == module or m.name.startswith(module + "(")]
    by_dev = defaultdict(list)
    for m in calls:
        by_dev[m.device].append(m)
    for ms in by_dev.values():
        ms.sort(key=lambda m: m.start)
    starts = {d: [m.start for m in ms] for d, ms in by_dev.items()}
    joined, total = defaultdict(float), defaultdict(float)
    for e in trace.ops:
        ms = by_dev.get(e.device)
        if not ms:
            continue
        i = bisect.bisect_right(starts[e.device], e.start) - 1
        if i < 0 or e.end > ms[i].end:
            continue
        total[ms[i].name] += e.seconds
        if _key(e.name) in keys:
            joined[ms[i].name] += e.seconds
    decode = {n for n, t in total.items() if t > 0 and joined[n] >= MIN_JOINED * t}
    return [m for m in calls if m.name in decode]
