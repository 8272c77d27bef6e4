"""Reduction from a profiler trace (.xplane.pb) to device metrics.

Reads the trace with `jax.profiler.ProfileData` and nothing else. For each
device plane (`/device:TPU:<n>`):

  busy      union of the intervals in which an operation ran (line
            "XLA Ops"; a nested operation adds nothing to the union)
  by name   SELF time of each operation: its duration minus what its
            children on the same line cover, summed per name
  modules   executions of whole programs (line "XLA Modules"), per name:
            the whole ones with their time, and all of them counted by the
            share of each that lies inside the interval. An execution that
            was running when the profiler's session began or ended is in
            the trace as a SHORTER event, cut to the session: one under
            WHOLE_SHARE of its program's longest is no whole execution, and
            counts by what lies inside over the longest one's length
  gaps      the idle intervals between operations

all cut to a sub-interval [t0_ns, t1_ns] of the trace when one is given.
Host planes are only searched for the benchmark's own anchor annotation,
which ties the trace's clock to the host's epoch clock (coarsely: the
device lines are aligned to the host by the profiler, not by us).

Checked on the small recorded trace under benchmark/tests/data/.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from types import SimpleNamespace

WHOLE_SHARE = 0.9  # of the program's longest execution: shorter was cut short
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "bench_anchor"
DROPPED = "Trace Buffers Dropped"  # the device's trace buffer ran over


@dataclass
class PlaneReduction:
    name: str
    busy_ns: float = 0.0
    self_ns: dict = field(default_factory=dict)      # op name -> self time
    total_ns: dict = field(default_factory=dict)     # op name -> inclusive
    count: dict = field(default_factory=dict)        # op name -> events
    modules: dict = field(default_factory=dict)      # name -> (whole runs, ns)
    executions: dict = field(default_factory=dict)   # name -> runs, cut ones by share
    gaps: list = field(default_factory=list)         # (start_ns, end_ns)


@dataclass
class Reduction:
    window_ns: float
    t0_ns: float
    t1_ns: float
    planes: list
    anchor_ns: float | None  # where the anchor annotation sits in the trace
    dropped_from_ns: float | None = None  # device events are missing from here on

    @property
    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the device planes."""
        return sum(p.busy_ns for p in self.planes) / len(self.planes) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def self_seconds(self, group=None) -> dict:
        """Self seconds per operation name (or per `group(name)`), summed
        over the device planes."""
        out: dict[str, float] = {}
        for p in self.planes:
            for name, ns in p.self_ns.items():
                key = group(name) if group else name
                out[key] = out.get(key, 0.0) + ns / 1e9
        return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _clip(events, t0, t1):
    """(start, end, name) cut to [t0, t1], dropping what falls outside."""
    for s, e, name in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            yield s, e, name


def _union_and_gaps(events, t0, t1):
    """Union length of intervals and the gaps between them in [t0, t1]."""
    busy, gaps, cursor = 0.0, [], t0
    for s, e, _ in sorted(events):
        if s > cursor:
            gaps.append((cursor, s))
        if e > cursor:
            busy += e - max(s, cursor)
            cursor = e
    if cursor < t1:
        gaps.append((cursor, t1))
    return busy, gaps


def _self_times(events):
    """Per-name self time on one line: a stack sweep over nested events."""
    self_ns: dict[str, float] = {}
    total_ns: dict[str, float] = {}
    count: dict[str, int] = {}
    stack: list[list] = []  # [end, name, child_ns, start]

    def close(item):
        end, name, child, start = item
        self_ns[name] = self_ns.get(name, 0.0) + max(0.0, end - start - child)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        stack.append([e, name, 0.0, s])
        total_ns[name] = total_ns.get(name, 0.0) + (e - s)
        count[name] = count.get(name, 0) + 1
    while stack:
        close(stack.pop())
    return self_ns, total_ns, count


def _line_events(line):
    return [
        (float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
        for ev in line.events
    ]


_HLO = re.compile(r"^(%\S+) = \(?(\w+\[[\d,]*\])?.*? ([\w\-]+)\(")


def op_group(name: str) -> str:
    """A device operation's trace name is its whole HLO instruction; group
    instructions by what they do: opcode (a Mosaic kernel by its call
    target) and result shape. `%closed_call.346 = u32[16,13824]{...}
    custom-call(...), custom_call_target="tpu_custom_call"` ->
    `custom-call:tpu_custom_call u32[16,13824]`."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    opcode = m.group(3)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target:
        opcode += ":" + target.group(1)
    return f"{opcode} {m.group(2) or 'tuple'}"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def _rehearsal_lines(plane):
    """A CPU rehearsal has no device plane: XLA's CPU client runs the
    operations on host threads. Their events stand in for the "XLA Ops" line
    so that the reduction's code runs in the sandbox — never a device time."""
    evs = [
        ev for ln in plane.lines if ln.name.startswith("tf_XLA")
        for ev in ln.events if ev.duration_ns > 0
    ]
    return {OPS_LINE: SimpleNamespace(events=evs)} if evs else {}


@dataclass
class Loaded:
    """One parse of a trace: each device plane's operation and program events,
    where the anchor annotation sits, and where dropped buffers begin."""
    path: str
    planes: list              # (name, ops, modules), events as (start, end, name)
    anchor_ns: float | None
    dropped_from_ns: float | None


def load_trace(path: str, rehearsal: bool = False) -> Loaded:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, anchor, dropped = [], None, None
    for plane in data.planes:
        if is_device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
        else:
            if anchor is None:
                anchor = next(
                    (float(ev.start_ns) for ln in plane.lines
                     for ev in ln.events if ev.name == ANCHOR), None)
            if not (rehearsal and plane.name == "/host:CPU"):
                continue
            lines = _rehearsal_lines(plane)
        if OPS_LINE not in lines and MODULES_LINE not in lines:
            continue
        for name, ln in lines.items():
            if name == OPS_LINE:
                continue  # millions of events; the mark is on a line of its own
            for ev in ln.events:
                if ev.name == DROPPED:
                    start = float(ev.start_ns)
                    dropped = start if dropped is None else min(dropped, start)
        device.append((
            plane.name,
            _line_events(lines.get(OPS_LINE) or lines[MODULES_LINE]),
            _line_events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
        ))
    if not device:
        raise ValueError(
            f"{path}: no device plane with an {OPS_LINE!r} line "
            f"(planes: {[p.name for p in data.planes]})"
        )
    return Loaded(path, device, anchor, dropped)


def _longest(mods) -> dict:
    """Per program, the length of its longest execution in the trace."""
    out: dict[str, float] = {}
    for s, e, mod in mods:
        out[mod] = max(out.get(mod, 0.0), e - s)
    return out


def reduce_loaded(loaded: Loaded, t0_ns: float | None = None,
                  t1_ns: float | None = None) -> Reduction:
    """Reduce a loaded trace over [t0_ns, t1_ns]; without them, from the
    first to the last device operation."""
    path = loaded.path
    starts = [s for _, ops, _ in loaded.planes for s, _, _ in ops]
    ends = [e for _, ops, _ in loaded.planes for _, e, _ in ops]
    if not starts:
        raise ValueError(f"{path}: no operation ran on the device")
    t0 = min(starts) if t0_ns is None else t0_ns
    t1 = max(ends) if t1_ns is None else t1_ns
    if loaded.dropped_from_ns is not None:
        # a trace that overran its buffer holds no device event after the
        # drop began: reduce what came before it, never the hole
        t1 = min(t1, loaded.dropped_from_ns)
        if t1 <= t0:
            raise ValueError(f"{path}: the trace dropped its buffers before "
                             "the interval asked for")
    planes = []
    for name, ops, mods in loaded.planes:
        ops = list(_clip(ops, t0, t1))
        red = PlaneReduction(name)
        red.busy_ns, red.gaps = _union_and_gaps(ops, t0, t1)
        red.self_ns, red.total_ns, red.count = _self_times(ops)
        whole_ns = _longest(mods)
        for s, e, mod in mods:
            inside = min(e, t1) - max(s, t0)
            if inside <= 0 or e <= s:
                continue
            cut_short = e - s < WHOLE_SHARE * whole_ns[mod]
            red.executions[mod] = red.executions.get(mod, 0.0) + inside / (
                whole_ns[mod] if cut_short else e - s)
            if t0 <= s and e <= t1 and not cut_short:  # a whole execution
                n, ns = red.modules.get(mod, (0, 0.0))
                red.modules[mod] = (n + 1, ns + (e - s))
        planes.append(red)
    return Reduction(t1 - t0, t0, t1, planes, loaded.anchor_ns,
                     loaded.dropped_from_ns)


def reduce_trace(path: str, t0_ns: float | None = None,
                 t1_ns: float | None = None, rehearsal: bool = False) -> Reduction:
    return reduce_loaded(load_trace(path, rehearsal), t0_ns, t1_ns)


SHORT_GAP_NS = 100_000  # gaps under 0.1 ms lie between operations of one program


def label_gaps(gaps, spans, epoch_of_ns, names) -> list[tuple[str, float]]:
    """Idle seconds by what the host was doing, coarsely: each gap of 0.1 ms
    or more goes to the one of `names` whose recorder spans (epoch seconds)
    cover most of it, or to "none"; shorter ones, which lie between the
    operations of a running program, are summed as "within_program".
    `epoch_of_ns` maps a trace time to the host's epoch clock."""
    by_name = {n: sorted((s[1], s[2]) for s in spans if s[0] == n) for n in names}
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            out["within_program"] = out.get("within_program", 0.0) + (g1 - g0) / 1e9
            continue
        a, b = epoch_of_ns(g0), epoch_of_ns(g1)
        best, best_cover = "none", 0.0
        for n, ivs in by_name.items():
            cover = sum(max(0.0, min(b, e) - max(a, s)) for s, e in ivs
                        if e > a and s < b)
            if cover > best_cover:
                best, best_cover = n, cover
        out[best] = out.get(best, 0.0) + (b - a)
    return sorted(out.items(), key=lambda kv: -kv[1])


def summarize(path: str, top: int = 25) -> dict:
    """What is in a trace: planes, lines, event counts and the top names —
    for looking at one by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for ln in plane.lines:
            evs = _line_events(ln)
            tot: dict[str, float] = {}
            for s, e, name in evs:
                tot[name] = tot.get(name, 0.0) + (e - s)
            lines[ln.name] = {
                "events": len(evs),
                "top_ns": sorted(tot.items(), key=lambda kv: -kv[1])[:top],
            }
        out[plane.name] = lines
    return out
