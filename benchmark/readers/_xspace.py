"""What the trace readers of the launch stages take from the profiler's
XSpace (not a reader itself): the program's `handel/<stage>` annotations on
the host planes (`jax.profiler.TraceAnnotation`, stats `seq` and `lane`) and
the executions of whole programs on each device plane's "XLA Modules" line —
both on the profiler's clock, in one file. The "XLA Ops" line (millions of
events) is never opened. The XSpace is the one `run.py` wrote for this cell
and has not removed yet; it is parsed once for all the readers of a run."""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field

import spec
import trace_reduce

PREFIX = "handel/"


@dataclass
class Stages:
    # (stage, seq, lane, start_ns, end_ns), by end
    annotations: list = field(default_factory=list)
    # device plane name -> [(start_ns, end_ns, program)], by start
    executions: dict = field(default_factory=dict)

    def of(self, stage: str, lane: int | None = None) -> list:
        return [a for a in self.annotations
                if a[0] == stage and (lane is None or a[2] == lane)]


def lane_of(plane_name: str) -> int:
    """/device:TPU:2 -> 2: a plane of pinned engines gives chip n lane n."""
    m = re.search(r"(\d+)$", plane_name)
    return int(m.group(1)) if m else 0


def parse(path: str) -> Stages:
    from jax.profiler import ProfileData

    out = Stages()
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.is_device_plane(plane.name):
            for ln in plane.lines:
                if ln.name == trace_reduce.MODULES_LINE:
                    out.executions[plane.name] = sorted(
                        trace_reduce._line_events(ln))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:  # one line a host thread
            for ev in ln.events:
                if not ev.name.startswith(PREFIX):
                    continue
                st = dict(ev.stats)
                start = float(ev.start_ns)
                out.annotations.append((
                    ev.name[len(PREFIX):], st.get("seq"), st.get("lane", 0),
                    start, start + float(ev.duration_ns)))
    out.annotations.sort(key=lambda a: a[4])
    return out


_parsed: dict = {}  # (path, mtime) -> Stages


def load(ctx) -> Stages | None:
    """The stages of this run's trace; None where there is no trace, or no
    `handel/` annotation in it (a program from before the annotations)."""
    trace_dir = os.path.join(spec.BENCH_DIR, "_out", "trace", ctx.cell.name)
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _parsed:
        _parsed.clear()
        _parsed[key] = parse(path)
    return _parsed[key] if _parsed[key].annotations else None
