"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The traced part of a run is marked by the harness's ``chipbench.window``
host span; everything is clipped to it.  Device activity comes from the
TPU planes (``/device:TPU:<i>``): their ``XLA Ops`` line gives the
operations (busy time, kernel time) and their ``XLA Modules`` line the
compiled programs (time per call).  A trace recorded on the CPU has no
device plane; there the operations are the host events that carry an
``hlo_op`` stat, and the programs are grouped by their ``hlo_module``.

* busy time: the union of operation intervals in the window, averaged
  over the devices; ``idle = 1 - busy / window``;
* idle gaps: the stretches of the window with no operation on a device,
  each named by the innermost host span that contains its midpoint;
* breakdown: the operations with the most device time, and the idle
  time summed by host span.
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
TOP = 10
# Control-flow ops enclose the ops of their bodies on the same line; they
# count towards busy time but would hide those ops in a ranking.
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\s]")


def short_name(name: str) -> str:
    """``%fusion.18 = s32[43259]{...} fusion(...)`` -> ``%fusion.18 s32[43259]``
    (TPU op events carry the whole HLO instruction as their name)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    return f"{head} {rest.split('{', 1)[0].split(' ', 1)[0]}"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns
    device: int  # device index, or -1 for a host event
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9

    def stat(self, key: str) -> Optional[str]:
        for k, v in self.stats:
            if k == key:
                return v
        return None


def _stats(ev) -> Tuple[Tuple[str, str], ...]:
    try:
        return tuple((str(k), str(v)) for k, v in ev.stats)
    except Exception:  # a stat value the bindings cannot convert
        return ()


def union_length(intervals: List[Tuple[int, int]]) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]
    ops: List[Event]  # device operations, clipped to the window
    modules: List[Event]  # device programs (one per call), in the window
    host: List[Event]  # host spans overlapping the window
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _per_device(self) -> Dict[int, List[Tuple[int, int]]]:
        per: Dict[int, List[Tuple[int, int]]] = defaultdict(list)
        for e in self.ops:
            per[e.device].append((e.start, e.end))
        return per

    @property
    def busy_s(self) -> float:
        per = self._per_device()
        n = max(self.n_devices, 1)
        return sum(union_length(iv) for iv in per.values()) / n / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def matching(self, pattern: str, where: str = "ops") -> List[Event]:
        """Events whose name (or ``long_name``/``hlo_module`` stat) matches."""
        rx = re.compile(pattern)
        pool = self.ops if where == "ops" else self.modules
        return [
            e for e in pool
            if rx.search(e.name) or rx.search(e.stat("long_name") or "")
            or rx.search(e.stat("hlo_module") or "")
        ]

    def _name_points(self, points: List[int]) -> List[str]:
        """The innermost host span containing each time (sorted input)."""
        spans = sorted((h for h in self.host if h.name != WINDOW_SPAN),
                       key=lambda h: h.start)
        heap: List[Tuple[int, int, int, str]] = []  # (duration, end, order, name)
        names, i = [], 0
        for t in points:
            while i < len(spans) and spans[i].start <= t:
                h = spans[i]
                heapq.heappush(heap, (h.end - h.start, h.end, i, h.name))
                i += 1
            # Drop spans that ended before t; the innermost live one is on top
            # once every shorter span above it has been dropped.
            while heap and heap[0][1] < t:
                heapq.heappop(heap)
            names.append(heap[0][3] if heap else "(no host span)")
        return names

    def idle_gaps(self) -> List[Tuple[str, float]]:
        per = self._per_device()
        totals: Dict[str, float] = defaultdict(float)
        for dev in range(max(self.n_devices, 1)):
            gs = gaps(per.get(dev, []), *self.window)
            for (s, e), name in zip(gs, self._name_points([(s + e) // 2 for s, e in gs])):
                totals[name] += (e - s) / 1e9
        return sorted(totals.items(), key=lambda kv: -kv[1])

    def top_ops(self) -> List[Tuple[str, float]]:
        totals: Dict[str, float] = defaultdict(float)
        for e in self.ops:
            if not CONTAINERS.match(e.name):
                totals[short_name(e.name)] += e.seconds
        return sorted(totals.items(), key=lambda kv: -kv[1])

    def breakdown(self) -> Dict[str, List[List]]:
        return {
            "device_ops": [[k, v] for k, v in self.top_ops()[:TOP]],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:TOP]],
        }


def find(path: Path) -> Path:
    files = sorted(glob.glob(str(Path(path) / "**" / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return Path(files[-1])


def _device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(path: Path) -> Reduced:
    """Read the trace under ``path`` (a directory or a ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    path = Path(path)
    pd = ProfileData.from_file(str(path if path.suffix == ".pb" else find(path)))
    host: List[Event] = []
    ops: List[Event] = []
    modules: List[Event] = []
    cpu_ops: List[Event] = []
    devices = set()
    for plane in pd.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            for ev in line.events:
                start = int(ev.start_ns)
                e = Event(ev.name, start, start + int(ev.duration_ns),
                          -1 if dev is None else dev, _stats(ev))
                if dev is not None:
                    devices.add(dev)
                    if line.name == "XLA Ops":
                        ops.append(e)
                    elif line.name == "XLA Modules":
                        modules.append(e)
                elif plane.name.startswith("/host"):
                    if e.stat("hlo_op") is not None:
                        cpu_ops.append(dataclasses.replace(e, device=0))
                    else:
                        host.append(e)
    if not devices:
        # A CPU trace: the "device" is the host's XLA thread pool.
        ops = cpu_ops
        by_call: Dict[Tuple[str, str], List[Event]] = defaultdict(list)
        for e in cpu_ops:
            by_call[(e.stat("hlo_module") or "", e.stat("run_id") or "")].append(e)
        modules = [
            Event(mod, min(x.start for x in evs), max(x.end for x in evs), 0,
                  (("hlo_module", mod),))
            for (mod, _), evs in by_call.items()
        ]
        devices = {0} if cpu_ops else set()
    spans = [h for h in host if h.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = spans[0].start, spans[0].end

    def clip(evs: List[Event]) -> List[Event]:
        out = []
        for e in evs:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                out.append(dataclasses.replace(e, start=s, end=t))
        return out

    return Reduced(
        window=(lo, hi),
        ops=clip(ops),
        modules=[m for m in modules if lo <= m.start and m.end <= hi],
        host=[h for h in host if h.end >= lo and h.start <= hi],
        n_devices=len(devices),
    )
