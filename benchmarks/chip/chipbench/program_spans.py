"""The program's own spans in a traced run.

The program marks its phases with host spans named ``repro.<phase>``, each
with its stats (``unit``, ``bytes``, ``event``, ...; ``repro.core.trace``).
``spans(run)`` reads them from the ``.xplane.pb`` the harness wrote under
``run.workdir / "trace"``, keeps those that overlap the trace's ``window``,
clipped to it, and caches the list on the run (``run.program_spans``). A
run without a trace, or a program without such spans, has nothing to read:
the readers built on this module then return None.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, NamedTuple, Optional

from . import trace

PREFIX = "repro."


class Span(NamedTuple):
    name: str
    start: float            # ns, on the trace's clock
    end: float
    stats: dict


def _read(trace_dir: str, lo: float, hi: float) -> List[Span]:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    profile = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX) and e.end_ns >= lo \
                        and e.start_ns <= hi:
                    out.append(Span(e.name, max(float(e.start_ns), lo),
                                    min(float(e.end_ns), hi),
                                    dict(e.stats)))
    out.sort(key=lambda s: s.start)
    return out


def spans(run) -> List[Span]:
    """The run's program spans in its window, read once."""
    cached = getattr(run, "program_spans", None)
    if cached is None:
        cached = [] if run.trace is None else \
            _read(str(run.workdir / "trace"), *run.trace.window())
        run.program_spans = cached
    return cached


def named(run, name: str) -> List[Span]:
    return [s for s in spans(run) if s.name == name]


def seconds(run, name: str) -> Optional[float]:
    """Seconds in the window covered by spans ``name``; None if it has
    none."""
    found = named(run, name)
    if not found:
        return None
    return trace.covered_ns([(s.start, s.end) for s in found],
                            *run.trace.window()) / 1e9


def stat_sum(found: List[Span], stat: str) -> float:
    return sum(s.stats.get(stat, 0) for s in found)


def committed(run) -> int:
    return sum(bool(u["ok"]) for u in run.units)


def per_gb(run, name: str) -> Optional[float]:
    """Seconds of spans ``name`` per GB of raw dump accepted."""
    s = seconds(run, name)
    if s is None or not run.bytes_accepted:
        return None
    return s / (run.bytes_accepted / 1e9)


def per_unit(run, name: str) -> Optional[float]:
    """Seconds of spans ``name`` per committed unit."""
    s, ok = seconds(run, name), committed(run)
    return s / ok if s is not None and ok else None


def idle_by_span(run) -> Dict[str, float]:
    """The window's device-idle seconds (``trace.idle_gaps``), each gap
    given to the innermost program span at its midpoint, else ``none``."""
    lo, hi = run.trace.window()
    out: Dict[str, float] = {}
    for _, sec, start in trace.idle_gaps(run.trace, lo, hi):
        mid = start + sec * 1e9 / 2
        inside = [s for s in spans(run) if s.start <= mid < s.end]
        name = min(inside, key=lambda s: s.end - s.start).name \
            if inside else "none"
        out[name] = out.get(name, 0.0) + sec
    return out
