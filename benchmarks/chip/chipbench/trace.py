"""From a JAX profiler trace to device busy time, kernel time and idle gaps.

``extract`` turns the profiler's planes into two plain lists, on the
trace's own clock in nanoseconds:

* device ops: ``(name, module, start, end, device)`` from the ``XLA Ops``
  line of every ``/device:TPU:<n>`` plane, each op given the XLA module
  (``XLA Modules`` line) whose interval holds it;
* host spans: ``(name, start, end)`` of the harness's own annotations
  (``window``, ``stage.<name>``, ``unit.<name>``, ``ingest.batch``).

Everything after that is arithmetic on those lists, so the tests check it
on a small recorded trace and on hand-made ones.

The device planes' clock is not the host's: in a v5e trace the device's
events sit 1.3-1.9 ms before the host calls that dispatched them (the
recorded trace in ``tests/data``). Nothing here realigns them; against
windows and stage spans of seconds that is below a thousandth.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIXES = ("window", "stage.", "unit.", "ingest.batch")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Op = Tuple[str, str, float, float, int]
Span = Tuple[str, float, float]


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    devices: List[int]

    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s[0] == "window"]
        if not w:
            raise ValueError("trace holds no 'window' span")
        return w[0][1], w[0][2]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def module_name(name: str) -> str:
    """``jit__qa_call(1234...)`` -> ``jit__qa_call``."""
    return name.split("(", 1)[0]


def _device_index(plane_name: str) -> Optional[int]:
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def extract(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to device ops and host spans."""
    ops: List[Op] = []
    spans: List[Span] = []
    devices = set()
    for plane in profile.planes:
        dev = _device_index(plane.name)
        lines = {ln.name: ln for ln in plane.lines}
        if dev is not None:
            if OPS_LINE not in lines:
                continue
            devices.add(dev)
            mods = sorted((e.start_ns, e.end_ns, e.name)
                          for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            for e in lines[OPS_LINE].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= e.start_ns \
                    else ""
                ops.append((op_name(e.name), module_name(mod),
                            float(e.start_ns), float(e.end_ns), dev))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIXES):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns)))
    ops.sort(key=lambda o: o[2])
    spans.sort(key=lambda s: s[1])
    return Trace(ops, spans, sorted(devices))


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return extract(ProfileData.from_file(max(files, key=os.path.getmtime)))


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered_ns(intervals, lo, hi) -> float:
    return sum(b - a for a, b in union(intervals, lo, hi))


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in ``[lo, hi]`` in which an op ran, averaged over devices."""
    if not trace.devices:
        return 0.0
    per = [covered_ns([(o[2], o[3]) for o in trace.ops if o[4] == d], lo, hi)
           for d in trace.devices]
    return sum(per) / len(per) / 1e9


def ops_in_spans(trace: Trace, prefix: str) -> List[Op]:
    """Device ops that start inside a host span whose name starts with
    ``prefix`` (a stage's dispatch runs inside its span, which ends only
    once its outputs are ready)."""
    spans = [(s[1], s[2]) for s in trace.spans if s[0].startswith(prefix)]
    starts = [s[0] for s in spans]
    out = []
    for o in trace.ops:
        i = bisect.bisect_right(starts, o[2]) - 1
        if i >= 0 and o[2] < spans[i][1]:
            out.append(o)
    return out


def top_ops(ops: Sequence[Op], n: int = 10) -> List[List]:
    """The ``n`` ops (``module/op``) with the most device seconds, summed
    over their launches."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for o in ops:
        tot[f"{o[1]}/{o[0]}" if o[1] else o[0]] += (o[3] - o[2]) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: float, hi: float,
              extra: Sequence[Span] = ()) -> List[Tuple[str, float, float]]:
    """Every interval in ``[lo, hi]`` in which no op ran on device 0 of the
    trace, named by what the host was doing at its midpoint: the innermost
    span of the harness (``stage.<name>``, ``ingest.batch``), a
    ``compile`` span from ``extra`` where one covers it, ``unit.outside_stage``
    inside a unit but outside its stage, else ``harness``."""
    dev = trace.devices[0] if trace.devices else None
    busy = union([(o[2], o[3]) for o in trace.ops if o[4] == dev], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in trace.spans if s[0] != "window"] + list(extra)
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [s for s in spans if s[1] <= mid < s[2]]
        names = {s[0] for s in inside}
        if "compile" in names:
            name = "compile"
        else:
            inner = [s for s in inside if s[0] != "compile"]
            inner.sort(key=lambda s: s[2] - s[1])
            name = inner[0][0] if inner else "harness"
            if name.startswith("unit."):
                name = "unit.outside_stage"
        out.append((name, (b - a) / 1e9, a))
    return out


def breakdown(trace: Trace, lo: float, hi: float,
              extra: Sequence[Span] = (), n: int = 10) -> dict:
    ops = [o for o in trace.ops if lo <= o[2] < hi]
    gaps = sorted(idle_gaps(trace, lo, hi, extra), key=lambda g: -g[1])
    return {"device_ops": top_ops(ops, n),
            "idle_gaps": [[g[0], g[1]] for g in gaps[:n]]}
