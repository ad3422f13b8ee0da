"""Driver: SMC-steered decoding of a backlog of requests through the
program's ``ServeEngine`` and ``Scheduler`` (a ``backlog`` mix).

The configuration's plain reference, ``chipbench/reference/<config>.py``,
makes the weights (``make_params``) and the logits they are checked
against (``logits``), so another configuration of a served family needs
its own files and no driver.

What the driver reads of the program is public: the tokens and the
resampling ancestors each tick commits (the scheduler's ``on_token``
stream), the slot range of each request and the pool's used blocks after
each tick (its ``SchedulerEventLog``), and the logits the engine's
``decode`` returns, the last of which the driver keeps.

Set-up does the same work for every seed: the weights are made from
``--seed`` on the device in one jitted call, the engine is built at the
configuration's sizes, every prompt length the mix can draw is prefilled
once into a throwaway cache, and a throwaway wave of requests that
resample at every token fills every slot range for ``throwaway_ticks``
ticks and is cancelled, so that the fork, clone and departure paths are
compiled.  Then the backlog is submitted and ``ticks_after_admission``
ticks admit its first requests into every slot.

The window steps ``Scheduler.step()`` tick after tick, each tick inside a
``chipbench.serve.tick`` span, until ``--seconds`` have passed, and keeps
for each tick its wall interval, the request-tokens it committed, the
resampling events, the KV pool's used blocks and the rows' contexts.

After the window every request in flight is compared with the float32
reference, ``particles`` of its particles drawn from the seed: a
particle's history is its request's token stream with the resampling
ancestors applied (``stream_tokens``), and the logits that each of the
window's last ``ticks`` ticks produced for the row that held its lineage
then are compared with the reference's at that position.  With
``ctx.control`` the reference with float8 products takes the program's
place.
"""

from __future__ import annotations

import gc
import math
import time
from collections import defaultdict, deque
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

from chipbench import backlog as backlog_lib
from chipbench import traffic as traffic_lib
from chipbench.bench import PACKAGE, load_module

WEIGHT_STREAM, KEY_STREAM, SAMPLE_STREAM, WARM_STREAM = 3, 4, 5, 6
TICK_SPAN = "chipbench.serve.tick"
WARM = "warm"  # the throwaway wave's request ids start with this
ALWAYS_RESAMPLE = 2.0  # an ESS threshold above 1 resamples at every token


def config_module(kind: str, cell):
    """The configuration's own file of a kind, ``chipbench/<kind>/<config>.py``:
    its plain reference (with the weights it is compared on) or its counts."""
    path = Path(__file__).resolve().parents[1] / kind / f"{cell.config_name}.py"
    return load_module(path, f"{PACKAGE}_{kind}_" + cell.config_name.replace(".", "_"))


def _engine_config(model: Dict[str, Any], serve: Dict[str, Any]):
    """The program's model and KV cache configurations at the cell's sizes."""
    from repro.models.config import ModelConfig
    from repro.serving.kv_cache import KVCacheConfig

    cfg = ModelConfig(**model)
    bs = int(serve["kv_block_size"])
    ccfg = KVCacheConfig(
        n_layers=cfg.n_layers,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd,
        block_size=bs,
        max_seqs=int(serve["max_seqs"]),
        max_blocks_per_seq=-(-int(serve["max_len"]) // bs),
        num_blocks=int(serve["kv_num_blocks"]),
        dtype=cfg.dtype,
    )
    return cfg, ccfg


def _engine(model: Dict[str, Any], serve: Dict[str, Any], params):
    from repro.models.model import LanguageModel
    from repro.serving.engine import ServeEngine

    cfg, ccfg = _engine_config(model, serve)
    return ServeEngine(LanguageModel(cfg), params, ccfg)


class LastLogits:
    """Keeps the logits the engine's ``decode`` returned last ([slots, V]),
    the timed path's own output, without copying them."""

    def __init__(self, eng):
        self.value = None
        real = eng.decode

        def decode(tokens, mask=None):
            self.value = real(tokens, mask)
            return self.value

        eng.decode = decode


def _warm_prefills(eng, lengths: List[int]) -> None:
    """Compile the prefill for every prompt length; the cache it fills is
    put back."""
    import jax
    import jax.numpy as jnp

    cache, slot = eng.cache, jnp.zeros((1,), jnp.int32)
    for n in lengths:
        jax.block_until_ready(eng.prefill(jnp.zeros((1, n), jnp.int32), slot))
        eng.cache = cache


def _lineage(ancestors: Dict[int, np.ndarray], row: int, steps: int) -> List[int]:
    """``slot[t]`` for t < steps: the row that held particle ``row``'s
    lineage when token t was fed.  A fork before token t gave row j the
    history of row ``anc[j]``."""
    slot = [0] * steps
    j = row
    for t in range(steps - 1, -1, -1):
        slot[t] = j
        anc = ancestors.get(t)
        if anc is not None:
            j = int(anc[j])
    return slot


def _slot_ranges(log) -> Dict[str, int]:
    """Each request's first slot, from its latest admission or resume."""
    return {e[1]: e[3] for e in log.events if e[0] in ("admit", "resume")}


def _used_blocks(log, since: int):
    steps = [e[3] for e in log.events[since:] if e[0] == "step"]
    return steps[-1] if steps else None


def run(ctx, devices) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.serving.scheduler import (DecodeRequest, Scheduler, SchedulerEventLog,
                                         stream_tokens)

    cell, seed = ctx.cell, ctx.seed
    model, serve, mix = cell.config["model"], cell.config["serve"], cell.traffic
    check_spec, warm_spec = cell.spec["check"], cell.spec["warm"]
    ref_lib = config_module("reference", cell)

    # -- set-up --------------------------------------------------------------
    reqs = backlog_lib.requests(mix, seed, int(model["vocab_size"]))
    plen = {r.rid: int(r.prompt.shape[0]) for r in reqs}
    params = ref_lib.make_params(model, traffic_lib.jax_key(seed, WEIGHT_STREAM))
    eng = _engine(model, serve, params)
    last = LastLogits(eng)
    log = SchedulerEventLog()
    stream: List[Any] = []  # every TokenEvent, in order
    sched = Scheduler(eng, event_log=log, on_token=stream.append)
    lengths = backlog_lib.prompt_lengths(mix)
    _warm_prefills(eng, lengths)

    per, steps = int(mix["particles"]), int(mix["steps"])
    warm_key = traffic_lib.jax_key(seed, WARM_STREAM)
    warm_ids = [f"{WARM}{i}" for i in range(eng.cache_cfg.max_seqs // per)]
    for i, rid in enumerate(warm_ids):
        sched.submit(DecodeRequest(rid=rid, prompt=jnp.zeros((lengths[0],), jnp.int32),
                                   n_particles=per, steps=steps,
                                   key=jax.random.fold_in(warm_key, i),
                                   ess_threshold=ALWAYS_RESAMPLE))
    for _ in range(int(warm_spec["throwaway_ticks"])):
        sched.step()
    for rid in warm_ids:
        sched.cancel(rid)
    keys = jax.random.split(traffic_lib.jax_key(seed, KEY_STREAM), len(reqs))
    for r, k in zip(reqs, keys, strict=True):
        sched.submit(DecodeRequest(rid=r.rid, prompt=jnp.asarray(r.prompt),
                                   n_particles=r.particles, steps=r.steps, key=k))
    for _ in range(int(warm_spec["ticks_after_admission"])):
        sched.step()
    ctx.log(f"set-up: {len(_slot_ranges(log)) - len(warm_ids)} requests admitted, "
            f"{sched.active_particles} of {sched.max_seqs} slots in use")

    # -- the window ----------------------------------------------------------
    ticks: List[Dict[str, Any]] = []
    window_rids = set()
    # The last ticks' logits, each with the step it fed each request.
    ring = deque(maxlen=int(check_spec["ticks"]))
    w0 = ctx.start_window()
    now = w0
    while now - w0 < ctx.seconds:
        traced = ctx.trace_due(now)
        mark, log_mark = len(stream), len(log.events)
        with jax.profiler.TraceAnnotation(TICK_SPAN):
            sched.step()
        t1 = time.perf_counter()
        new = [ev for ev in stream[mark:] if ev.token is not None]
        window_rids.update(ev.rid for ev in new)
        ring.append((last.value, {ev.rid: ev.t for ev in new}))
        ticks.append({
            "t0": now, "t1": t1, "traced": traced,
            "committed": len(new),
            "resamples": sum(1 for ev in new if ev.ancestors is not None),
            "used_blocks": _used_blocks(log, log_mark),
            "contexts": [plen[ev.rid] + ev.t + 1 for ev in new for _ in range(len(ev.token))],
        })
        now = t1
    ctx.end_window(now)
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices)
    ctx.log("ticks in the window (s): "
            + " ".join(f"{t['t1'] - t['t0']:.4f}" for t in ticks))

    # -- after the window: the sample, read out, then the program freed ------
    def in_flight():
        done = {ev.rid for ev in stream if ev.final}
        return sorted({ev.rid for ev in stream if ev.rid in plen} - done)

    for _ in range(2):  # a window that closed between two waves
        if in_flight():
            break
        mark = len(stream)
        sched.step()
        ring.append((last.value, {ev.rid: ev.t for ev in stream[mark:]
                                  if ev.token is not None}))
    by_rid = defaultdict(list)
    for ev in stream:
        by_rid[ev.rid].append(ev)
    finals = {ev.rid: ev.status for ev in stream if ev.final and ev.rid in plen}
    failed = sum(1 for rid, status in finals.items() if status != "ok")
    attempted = len((window_rids & set(plen)) | set(finals))
    if eng.oom:
        failed = attempted
    lo = _slot_ranges(log)
    pick = traffic_lib.rng(seed, SAMPLE_STREAM)
    want = int(check_spec["particles"])
    rows: List[Dict[str, Any]] = []
    for rid in in_flight():
        evs = [ev for ev in by_rid[rid] if ev.token is not None]
        n, t_done = len(evs[0].token), len(evs)
        hist = stream_tokens(evs, n=n, steps=t_done)
        ancestors = {ev.t: ev.ancestors for ev in evs if ev.ancestors is not None}
        prompt = next(r.prompt for r in reqs if r.rid == rid)
        for p in sorted(pick.choice(n, size=min(want, n), replace=False)):
            slot = _lineage(ancestors, int(p), t_done)
            rows.append({
                "tokens": np.concatenate([prompt, hist[p]]).astype(np.int32),
                "program": [(plen[rid] + fed[rid], np.asarray(lg[lo[rid] + slot[fed[rid]]]))
                            for lg, fed in ring if rid in fed],
            })
    del sched, eng, last, stream, by_rid, ring
    gc.collect()

    # -- correctness: the compared logits against the float32 reference -----
    pad_to = -(-int(serve["max_len"]) // 128) * 128
    rel_max, abs_max, compared = 0.0, 0.0, 0
    for r in rows:
        at = [pos for pos, _ in r["program"]]
        ref = np.asarray(ref_lib.logits(params, model, r["tokens"], pad_to, at))
        got = [lg for _, lg in r["program"]]
        if ctx.control:
            got = np.asarray(ref_lib.logits(params, model, r["tokens"], pad_to, at, fp8=True))
        for want_logits, lg in zip(ref, got, strict=True):
            diff = np.asarray(lg, np.float64) - want_logits
            rel = float(np.linalg.norm(diff) / np.linalg.norm(want_logits))
            err = float(np.max(np.abs(diff)))
            # A non-finite logit fails both numbers (max() would skip a NaN).
            rel_max = max(rel_max, rel if math.isfinite(rel) else math.inf)
            abs_max = max(abs_max, err if math.isfinite(err) else math.inf)
            compared += 1
    checks = [
        dict(name="particles_compared", value=len(rows), limit=f">= {want}",
             ok=len(rows) >= want),
        dict(name="logits_compared", value=compared, limit=f">= {want}",
             ok=compared >= want),
        dict(name="logits_rel_err_max", value=rel_max,
             limit=float(check_spec["logits_rel_err_max"]),
             ok=rel_max <= float(check_spec["logits_rel_err_max"])),
        dict(name="logits_abs_err_max", value=abs_max,
             limit=float(check_spec["logits_abs_err_max"]),
             ok=abs_max <= float(check_spec["logits_abs_err_max"])),
    ]
    return {
        "memory_peak_bytes": memory_peak,
        "attempted": attempted,
        "failed": failed,
        "checks": checks,
        "ticks": ticks,
    }
