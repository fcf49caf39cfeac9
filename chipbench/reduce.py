"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

The device planes (``/device:TPU:<n>``) carry one event per program
execution on their ``XLA Modules`` line. (Their ``XLA Ops`` line holds
one event per operation of every while-loop iteration, millions a
second; it is not read.) The host plane carries the benchmark's own
``jax.profiler.TraceAnnotation`` spans, named ``chipbench.*``, on the
same clock. From those:

* busy seconds: the union of the program executions inside the traced
  window, averaged over the devices that ran anything;
* device seconds per program (module name without its ``(id)``), and
  the programs that took the most;
* idle gaps: the stretches of the window with no program running, each
  named by the innermost benchmark span that covers it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from chipbench import stats

WINDOW = "chipbench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float, str]


@dataclasses.dataclass
class Trace:
    """Events in seconds on the trace's own clock."""

    modules: Dict[str, List[Interval]]      # device plane -> executions
    host: List[Interval]                    # chipbench.* annotations

    def window(self, name: str = WINDOW) -> Optional[Tuple[float, float]]:
        spans = [(s, e) for s, e, n in self.host if n == name]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def program_name(module: str) -> str:
    return _MODULE_ID.sub("", module)


MODULES = "XLA Modules"


def from_events(events) -> Trace:
    """Build from ``(plane, line, name, start_ns, duration_ns)`` rows."""
    modules: Dict[str, List[Interval]] = collections.defaultdict(list)
    host: List[Interval] = []
    for plane, line, name, start_ns, dur_ns in events:
        iv = (start_ns / 1e9, (start_ns + dur_ns) / 1e9, name)
        if plane.startswith("/device:"):
            if line == MODULES:
                modules[plane].append(iv)
        elif plane.startswith("/host:") and name.startswith("chipbench."):
            host.append(iv)
    return Trace(dict(modules), host)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` with nothing but JAX, keeping only the
    lines and spans that the reduction reads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    rows = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name != MODULES:
                continue
            for ev in line.events:
                name = ev.name
                if device or name.startswith("chipbench."):
                    rows.append((plane.name, line.name, name, ev.start_ns,
                                 ev.duration_ns))
    return from_events(rows)


def _clip(ivs: List[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(s, t0), min(e, t1), n) for s, e, n in ivs
            if e > t0 and s < t1]


def busy_s(tr: Trace, t0: float, t1: float) -> float:
    """Seconds a program ran, averaged over the devices that ran one."""
    per = [stats.union_length((s, e) for s, e, _ in _clip(ivs, t0, t1))
           for ivs in tr.modules.values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else 0.0


def program_seconds(tr: Trace, t0: float, t1: float) -> Dict[str, float]:
    """Device seconds per program, summed over devices."""
    out: Dict[str, float] = collections.defaultdict(float)
    for ivs in tr.modules.values():
        for s, e, n in _clip(ivs, t0, t1):
            out[program_name(n)] += e - s
    return dict(out)


def top_programs(tr: Trace, t0: float, t1: float, k: int = 10
                 ) -> List[list]:
    """The `k` programs with the most device seconds."""
    got = program_seconds(tr, t0, t1)
    return [[n, v] for n, v in sorted(got.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, t0: float, t1: float, k: int = 10) -> List[list]:
    """The `k` longest stretches with no program on the busiest device,
    each named by the innermost benchmark span around its middle."""
    if not tr.modules:
        return [["no device activity", t1 - t0]]
    plane = max(tr.modules, key=lambda p: stats.union_length(
        (s, e) for s, e, _ in _clip(tr.modules[p], t0, t1)))
    holes = stats.gaps([(s, e) for s, e, _ in tr.modules[plane]], t0, t1)
    holes.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in holes[:k]:
        mid = (s + e) / 2
        around = [(he - hs, n) for hs, he, n in tr.host
                  if hs <= mid <= he and n != WINDOW]
        out.append([min(around)[1] if around else "between requests", e - s])
    return out
