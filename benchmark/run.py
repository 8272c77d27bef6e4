#!/usr/bin/env python3
"""The benchmark's one command: one cell, one seed, one window.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It refuses to run without the TPU and the chips the cell asks
for (no CPU fallback), builds the registry, the keys and the request pool
from `--seed` with the benchmark's own reference code, builds the program's
device engine and `BatchVerifierService(fallback=None)` from what the
configuration file states, warms every launch class the traffic file names
through the engine's own dispatch/fetch and holds the program's class
counters to it, drives the service through a ramp and then the measured
window, compares what was served with the plain reference, and
prints phase lines and then ONE result line (the contract's keys).

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics (own span sink on the service, profiler over the window's
last seconds).

`--rehearse` is for the sandbox, which has no chip: it skips the look for a
TPU, cuts lanes, clients and pool, and reports NO metric and `correct:
false` — a rehearsal shows that the path runs, never a number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, for setup_s

import argparse
import asyncio
import gc
import importlib
import json
import os
import shutil
import statistics
import sys
from collections import Counter

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)  # the benchmark's own modules
sys.path.insert(1, ROOT)       # the program under test

import correct  # noqa: E402
import loadgen  # noqa: E402
import spec  # noqa: E402
import traffic as tg  # noqa: E402
import trace_reduce  # noqa: E402
from spans import SpanSink  # noqa: E402

OUT_DIR = os.path.join(BENCH_DIR, "_out")
# the traced run profiles the window's last half second: the range class puts 3
# million device events a second into the trace, and ending the session and
# reading them costs about 0.7 s a megabyte of trace (measured, PR 25)
TRACE_SECONDS = 0.5
TRACE_SETTLE = 0.1        # ... and reduces it from this far in
WARM_LAUNCHES = 3         # launches of each class through an engine before the service
REHEARSE = {"lanes": 4, "clients": 8, "pool_requests": 24, "forged_share": 0.1}
GAP_SPANS = ("dispatch_pack", "launch_queued", "launch_fetched")


class BenchFailure(Exception):
    """The run cannot produce a result."""


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


def resolve(path: str):
    """'package.module:attr' -> the attribute."""
    mod, attr = path.split(":")
    return getattr(importlib.import_module(mod), attr)


class GcMeter:
    """Collections of the interpreter's garbage collector, by when they ran:
    a collection holds the interpreter lock, so a long one stalls the loop
    and the packing thread alike."""

    def __init__(self):
        self.events, self._t = [], 0.0  # (start, seconds, generation)
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.events.append(
                (self._t, time.perf_counter() - self._t, info["generation"]))

    def between(self, t0: float, t1: float) -> dict:
        """{generation: [collections, seconds]} and the longest, in [t0, t1]."""
        out, longest = {}, 0.0
        for t, s, g in self.events:
            if t0 <= t < t1:
                n, tot = out.get(str(g), (0, 0.0))
                out[str(g)] = [n + 1, tot + s]
                longest = max(longest, s)
        return {"by_generation": out, "longest_ms": 1e3 * longest}


LONG_COLLECTION_S = 0.020  # a collection this long is looked for in the stalls


def stalls(done_times, t0: float, t1: float, collections=()) -> dict:
    """Where a rate below the steady one went: verdicts come back in bursts,
    one a launch; the steady interval is the median between bursts, and a
    STALL is an interval over 1.5 times that. `collections` are the
    collector's `(start, seconds, generation)` events: a stall that one of
    LONG_COLLECTION_S or more overlaps is counted under `with_collection`.
    From the records, after the window; costs the window nothing."""
    ts = sorted(t for t in done_times if t0 <= t <= t1)
    starts = [b for a, b in zip(ts, ts[1:]) if b - a > 0.005]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    if len(gaps) < 3:
        return {"bursts": len(starts)}
    steady = statistics.median(gaps)
    long = [(t, t + s) for t, s, _ in collections if s >= LONG_COLLECTION_S]
    late = [(a, b) for a, b in zip(starts, starts[1:]) if b - a > 1.5 * steady]
    under = [(a, b) for a, b in late if any(c < b and d > a for c, d in long)]
    lost = lambda pairs: 1e3 * sum(b - a - steady for a, b in pairs)
    return {"bursts": len(starts), "steady_interval_ms": 1e3 * steady,
            "stalls": len(late), "stalled_ms": lost(late),
            "longest_interval_ms": 1e3 * max(gaps),
            "stalls_with_collection": len(under),
            "stalled_ms_with_collection": lost(under)}


def slices(records, t0: float, t1: float, step: float = 10.0,
           collections=()) -> list[dict]:
    """The window as consecutive pieces of `step` seconds (the last takes the
    remainder): what each completed, the latency of the requests issued in
    it, and its `stalls`. One long run then reads as many short windows with
    one set-up. From the records, after the window; a window under two steps
    gives none. The pieces' candidates, requests and latency samples add up
    to the window's."""
    n = int((t1 - t0) / step)
    if n < 2:
        return []
    piece = lambda t: min(n - 1, int((t - t0) / step))
    done = [[] for _ in range(n)]
    lat = [[] for _ in range(n)]
    cands = [0] * n
    for r in records:
        if r.verdicts is None:
            continue
        if t0 <= r.done <= t1:
            k = piece(r.done)
            done[k].append(r.done)
            cands[k] += len(r.verdicts)
        if t0 <= r.due < t1:
            lat[piece(r.due)].append(r.done - r.due)
    out = []
    for k in range(n):
        a, b = t0 + k * step, (t0 + (k + 1) * step if k < n - 1 else t1)
        ls = sorted(lat[k])
        completion = stalls(done[k], a, b, collections)
        out.append({
            "from_s": a - t0, "seconds": b - a,
            "requests_completed": len(done[k]),
            "candidates_completed": cands[k],
            "candidates_per_s": cands[k] / (b - a),
            "latency_samples": len(ls),
            "p50_ms": 1e3 * statistics.median(ls) if ls else None,
            "p95_ms": 1e3 * percentile(ls, 0.95) if ls else None,
            "launches": completion["bursts"],
            "completion": completion,
            "collections": sum(a <= t < b for t, _, _ in collections),
            "longest_collection_ms": 1e3 * max(
                (s for t, s, _ in collections if a <= t < b), default=0.0),
        })
    return out


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


class ReaderContext:
    """What a per-layer reader may look at."""

    def __init__(self, cell, lanes, result, latencies_s, sink, trace):
        self.cell, self.lanes = cell, lanes
        self.result, self.latencies_s = result, latencies_s
        self.sink, self.trace = sink, trace
        self.counters0 = result.marks["t0"]["counters"]
        self.counters1 = result.marks["t1"]["counters"]

    def delta(self, key: str) -> float:
        return self.counters1[key] - self.counters0[key]


def read_metric(name: str, ctx: ReaderContext):
    """metrics/<name>.json -> readers/<reader>.py read(ctx, **args)."""
    m = spec.load_metric(name)
    reader = importlib.import_module(f"readers.{m['reader']}")
    return reader.read(ctx, **m.get("args", {}))


def find_devices(chips: int, rehearse: bool) -> dict:
    from handel_tpu.utils.jaxenv import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    say(phase="device", compile_cache_dir=cache_dir, rehearse=rehearse, **device)
    if rehearse:
        return device
    if device["platform"] != "tpu":
        raise BenchFailure(
            f"JAX found no TPU (platform {device['platform']!r}): a time "
            "from another backend is not a measurement of this system"
        )
    if len(devs) != chips:
        raise BenchFailure(f"the cell asks for {chips} chip(s), JAX sees {len(devs)}")
    if device["kind"] not in spec.load_peaks():
        raise BenchFailure(
            f"device kind {device['kind']!r} is not in benchmark/peaks.json"
        )
    return device


def build_requests(cell, ref, seed: int, lanes: int, rehearse: bool):
    """Registry, keys and the signed request pool, from the seed, with the
    reference's arithmetic only."""
    cfg, tr = cell.config, dict(cell.traffic)
    if rehearse:
        tr.update({k: v for k, v in REHEARSE.items() if k != "lanes"})
        if "arrival" in tr:  # a CPU launch takes half a second
            tr["arrival"] = dict(tr["arrival"], rate_rps=1.0)
    lo, hi = cfg["levels_served"]
    if not (lo <= tr["levels"][0] and tr["levels"][1] <= hi):
        raise BenchFailure(
            f"traffic levels {tr['levels']} leave the configuration's "
            f"levels_served {cfg['levels_served']}"
        )
    t0 = time.perf_counter()
    ref.load()  # builds the reference library, once per checkout
    t1 = time.perf_counter()
    stats: dict = {}
    points, pool, msg = tg.make_pool(cfg, tr, seed, ref, stats)
    ladder = cfg["guarantees"]["launch_classes"]
    by_class = Counter(
        tg.class_of(c.hull_holes(), ladder) for r in pool for c in r)
    say(phase="pool", reference_build_s=t1 - t0, keys=len(points),
        failing=cfg["deployment"]["failing"], requests=len(pool),
        candidates=sum(len(r) for r in pool),
        forged=sum(c.forged for r in pool for c in r),
        launch_classes=tr["launch_classes"],
        candidates_by_class={cls["name"]: by_class[i]
                             for i, cls in enumerate(ladder)},
        seconds=time.perf_counter() - t1, **stats)
    return tr, points, pool, msg


def to_program_types(cfg, points, pool):
    """Wrap the reference's raw points and ranges in the program's own key,
    signature and bitset types (what `verify` takes)."""
    PublicKey = resolve(cfg["program"]["public_key"])
    Signature = resolve(cfg["program"]["signature"])
    BitSet = resolve(cfg["program"]["bitset"])
    n = len(points)
    reqs = []
    for req in pool:
        out = []
        for c in req:
            bs = BitSet(n)
            bs.set_range(c.lo, c.lo + c.size)
            for i in c.holes:
                bs.set(i, False)
            out.append((bs, Signature(c.sig)))
        reqs.append(out)
    return [PublicKey(p) for p in points], reqs


def build_engines(cfg, pubkeys, lanes: int, chips: int):
    """The device engine its scheme's constructor prepares (one chip), or
    the configuration's plane of pinned engines (several)."""
    from handel_tpu.models.registry import new_scheme

    opts = cfg.get("device_options", {})
    if chips == 1:
        scheme = new_scheme(cfg["scheme"], batch_size=lanes, warmup=False, **opts)
        target = scheme.constructor.prepare(pubkeys)
        return target, [target]
    plane = resolve(cfg["program"]["plane"])(
        pubkeys, devices=chips, batch_size=lanes, **opts
    )
    return plane, [lane.engine for lane in plane.lanes]


def warm_batches(flat, ladder, named, lanes: int):
    """The warm launches of one engine: for every class of the configuration's
    `ladder` that the traffic names, narrowest first, WARM_LAUNCHES whole
    launches of the pool's candidates whose hull holes lie in that class's
    interval, in pool order, cycling. `flat` is [(request, candidate)];
    returns [(class name, k, [(request, candidate)] * lanes)]."""
    unknown = sorted(set(named) - {cls["name"] for cls in ladder})
    if unknown:
        raise BenchFailure(
            f"the traffic names launch class(es) {unknown}, which the "
            "configuration's guarantees.launch_classes does not have")
    out = []
    for cls in ladder:
        if cls["name"] not in named:
            continue
        lo, hi = cls["hull_holes"]
        own = [rc for rc in flat if lo <= rc[1].hull_holes() <= hi]
        if not own:
            raise BenchFailure(
                f"the traffic names launch class {cls['name']!r}, but the pool "
                f"holds no candidate with {lo}..{hi} hull holes to warm it with")
        out += [(cls["name"], k,
                 [own[(k * lanes + j) % len(own)] for j in range(lanes)])
                for k in range(WARM_LAUNCHES)]
    return out


def warm(engines, msg, reqs, pool, lanes, ladder, named, meter):
    """Compile and warm every launch class the traffic names (`warm_batches`)
    through each engine's own dispatch/fetch. Returns the launches made and
    how many of their verdicts differ from the construction."""
    import jax

    flat = [(req, c) for rs, cs in zip(reqs, pool) for req, c in zip(rs, cs)]
    batches = warm_batches(flat, ladder, named, lanes)
    launches = wrong = 0
    # the cyclic collector is off while the programs trace and lower: a full
    # collection that lands inside a launch's lowering doubles it, and where
    # it lands moves with every edit of the harness (PERF.md section 6, PR 37)
    gc.disable()
    try:
        for e in engines:
            if (any(name.startswith("range") for name in named)
                    and hasattr(type(e), "_prefix")):
                # the prefix table is built on the first range dispatch; build
                # it here so that its scan is timed apart (`type(e)`: asking
                # the engine itself would run the property, and build it
                # untimed)
                t0 = time.perf_counter()
                jax.block_until_ready(e._prefix)
                say(phase="prefix_table", seconds=time.perf_counter() - t0,
                    **meter.take())
            for name, k, batch in batches:
                t0 = time.perf_counter()
                got = e.fetch(e.dispatch(msg, [req for req, _ in batch]))
                wrong += sum(g == c.forged for g, (_, c) in zip(got, batch))
                launches += 1
                say(phase=f"warm_launch_{name}_{k}",
                    seconds=time.perf_counter() - t0, **meter.take())
    finally:
        gc.enable()
    return launches, wrong


class Tracer:
    """The profiler around the window's last seconds (traced run only)."""

    def __init__(self, workload: str, rehearsal: bool):
        self.rehearsal = rehearsal
        self.dir = os.path.join(OUT_DIR, "trace", workload)
        self.t_started_epoch = None
        self.stop_s = None  # what ending the session and writing it took

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # it slows the host it should watch
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_started_epoch = time.time()
        with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
            time.sleep(0.001)

    def stop(self):
        """End the trace and write its XSpace. `jax.profiler.stop_trace()`
        would also convert the trace for the trace viewer, which takes ten
        minutes at this size (measured, PR 25): the session's raw bytes are
        taken instead, through the same state `stop_trace` uses; where this
        JAX has no such state, `stop_trace` it is."""
        import jax
        from jax._src import profiler as jp

        t0 = time.perf_counter()
        try:
            state = getattr(jp, "_profile_state", None)
            session = getattr(state, "profile_session", None)
            if session is None or not hasattr(session, "stop"):
                jax.profiler.stop_trace()
                return
            with state.lock:
                data = session.stop()
                state.reset()
            out = os.path.join(self.dir, "plugins", "profile", "run")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, "bench.xplane.pb"), "wb") as f:
                f.write(data)
        finally:
            self.stop_s = time.perf_counter() - t0

    def reduce(self, t1_epoch: float):
        """Reduce [start + settle, window end], placed on the trace's clock
        through the anchor annotation."""
        path = trace_reduce.find_xplane(self.dir)
        loaded = trace_reduce.load_trace(path, rehearsal=self.rehearsal)
        anchor = loaded.anchor_ns
        if anchor is None:
            say(phase="trace", warning="no anchor annotation: whole trace reduced")
            red = trace_reduce.reduce_loaded(loaded)
            return red, path, (lambda ns: self.t_started_epoch + (ns - red.t0_ns) / 1e9)
        to_ns = lambda ep: anchor + (ep - self.t_started_epoch) * 1e9
        red = trace_reduce.reduce_loaded(
            loaded, to_ns(self.t_started_epoch + TRACE_SETTLE), to_ns(t1_epoch))
        return red, path, (lambda ns: self.t_started_epoch + (ns - anchor) / 1e9)


def run(args) -> dict:
    cell = spec.Cell(args.workload, args.benchmark)
    cfg = cell.config
    lanes = REHEARSE["lanes"] if args.rehearse else int(cfg["lanes"])
    device = find_devices(cell.chips, args.rehearse)
    import jax

    from compile_meter import CompileMeter

    meter = CompileMeter()
    gc_meter = GcMeter()
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    tr, points, pool, msg = build_requests(cell, ref, args.seed, lanes, args.rehearse)
    pubkeys, reqs = to_program_types(cfg, points, pool)

    t0 = time.perf_counter()
    target, engines = build_engines(cfg, pubkeys, lanes, cell.chips)
    say(phase="engines", engines=len(engines), lanes=lanes,
        scheme=cfg["scheme"], seconds=time.perf_counter() - t0, **meter.take())
    ladder, named = cfg["guarantees"]["launch_classes"], tr["launch_classes"]
    warm_launches, warm_wrong = warm(
        engines, msg, reqs, pool, lanes, ladder, named, meter)

    sink = SpanSink() if args.trace else None
    Service = resolve(cfg["program"]["service"])
    service = Service(target, fallback=None, recorder=sink,
                      **cfg.get("service_options", {}))
    tracer = Tracer(cell.name, args.rehearse) if args.trace else None
    # what was warmed, by the program's own class counters: a wrong interval
    # in a data file, or a ladder that moved, ends the run here and in words
    warm_checks = correct.warmed_classes(
        ladder, named, service.values(), WARM_LAUNCHES * len(engines))
    if not all(c.ok for c in warm_checks):
        raise BenchFailure("the engine did not warm the classes the traffic "
                           "names: " + "; ".join(
                               c.line() for c in warm_checks if not c.ok))

    def on_edge(edge: str) -> dict:
        return {"counters": dict(service.values()), "compile": meter.take()}

    timed = []
    if tracer:
        timed = [(max(0.0, args.seconds - TRACE_SECONDS), tracer.start),
                 (args.seconds, tracer.stop)]
    fresh = tr["dedup"] == "fresh_scope"
    scope_of = (lambda s, j: f"{s}/{j}") if fresh else (lambda s, j: "shared")
    ramp_s = float(tr.get("ramp_s", 2.0))

    async def drive():
        try:
            if tr["loop"] == "closed":
                starts = tg.client_order(len(reqs), tr["clients"], args.seed)
                return await loadgen.closed_loop(
                    service, msg, pubkeys, reqs, starts, scope_of, ramp_s,
                    args.seconds, on_edge, timed)
            offsets = tg.arrival_offsets(
                tr["arrival"], ramp_s + args.seconds, args.seed)
            return await loadgen.open_loop(
                service, msg, pubkeys, reqs, offsets, tr["clients"], scope_of,
                ramp_s, args.seconds, on_edge, timed)
        finally:
            service.stop()

    gc.collect()
    gc.freeze()  # set-up's objects out of the collector's way in the window
    say(phase="setup_done", seconds=time.perf_counter() - T_START,
        ramp_s=ramp_s, loop=tr["loop"], clients=tr["clients"])
    res = asyncio.run(drive())
    setup_s = res.t0 - T_START
    window_s = res.t1 - res.t0

    device["memory_peak_bytes"] = int(max(  # the fullest chip's peak
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ))

    # -- what the window served ---------------------------------------------
    sample = res.in_window()
    latencies = sorted(r.done - r.due for r in sample if r.verdicts is not None)
    failed = sum(r.verdicts is None for r in sample)
    done_in_window = [
        r for r in res.records
        if r.verdicts is not None and res.t0 <= r.done <= res.t1
    ]
    candidates_done = sum(len(r.verdicts) for r in done_in_window)
    c0, c1 = res.marks["t0"]["counters"], res.marks["t1"]["counters"]
    window_compile = res.marks["t1"]["compile"]
    names = cfg["guarantees"]  # the program's counter names
    delta = lambda key: c1[key] - c0[key]
    cut = {}  # the window in pieces: `slices` (10 s), `slices_<n>s`
    for step in args.slice_seconds:
        pieces = slices(res.records, res.t0, res.t1, step, gc_meter.events)
        if pieces:
            cut["slices" if step == 10 else f"slices_{step:g}s"] = pieces
    say(phase="window", seconds=window_s, setup_s=setup_s,
        latency_samples=len(latencies), attempted=len(sample), failed=failed,
        requests_completed_in_window=len(done_in_window),
        candidates_completed_in_window=candidates_done,
        launches=delta(names["served_by_device"]["launches"]),
        candidates_verified=delta(names["served_by_device"]["candidates"]),
        dedup_hits=delta(names["dedup_hits"]),
        launches_by_class={cls["name"]: delta(cls["counter"]) for cls in ladder},
        compile_events_in_window=window_compile["events"],
        compile_in_window=window_compile,
        gc_in_window=gc_meter.between(res.t0, res.t1),
        completion=stalls([r.done for r in res.records], res.t0, res.t1,
                          gc_meter.events),
        errors=sorted({r.error for r in res.records if r.error})[:3], **cut)

    # -- correct: the served answers against the reference -------------------
    t0 = time.perf_counter()
    verify_one = lambda c: ref.verify(msg, points, c.signers(), c.sig)
    checks, info = correct.compare(res.records, pool, args.seed, verify_one)
    final = dict(service.values())
    answered = sum(len(r.verdicts) for r in res.records if r.verdicts is not None)
    checks += correct.guarantees(
        cfg["guarantees"], final, answered, warm_launches,
        window_compile["events"],
        sum(r.verdicts is None for r in res.records), fresh,
    )
    checks.append(correct.Check(
        "warmup_verdicts_differing_from_construction", warm_wrong, 0))
    checks += warm_checks
    checks.append(correct.unwarmed_class_launches(ladder, named, final))
    say(phase="reference", seconds=time.perf_counter() - t0, **info)
    for c in checks:
        print(c.line(), flush=True)
    is_correct = all(c.ok for c in checks)

    # -- metrics -----------------------------------------------------------
    metrics: dict = {}
    breakdown = None
    if not args.trace:
        if not latencies:
            raise BenchFailure("no request completed in the window")
        values = {
            "verdict_p50_ms": 1e3 * statistics.median(latencies),
            "verdict_p95_ms": 1e3 * percentile(latencies, 0.95),
            "candidates_per_s": candidates_done / window_s,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        t_read = time.perf_counter()
        red, path, to_epoch = tracer.reduce(res.t1_epoch)
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        ctx = ReaderContext(cell, lanes, res, latencies, sink, red)
        for m in cell.per_layer():
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        ops = sorted(red.self_seconds(trace_reduce.op_group).items(),
                     key=lambda kv: -kv[1])[:10]
        gaps = [g for p in red.planes for g in p.gaps]
        breakdown = {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [
                [n, s] for n, s in trace_reduce.label_gaps(
                    gaps, sink.spans, to_epoch, GAP_SPANS)[:10]
            ],
        }
        # launch cadence from the service's spans, before the profiler ran
        # and under it: shows whether the traced interval stands for the window
        ends = sorted(sp[2] for sp in sink.named("launch_on_device",
                                                   res.t0_epoch, res.t1_epoch))
        cadence = lambda ts: (
            1e3 * (ts[-1] - ts[0]) / (len(ts) - 1) if len(ts) > 1 else None)
        say(phase="trace", xplane=os.path.relpath(path, ROOT),
            launch_interval_ms_before_trace=cadence(
                [t for t in ends if t < tracer.t_started_epoch - 0.5]),
            launch_interval_ms_under_trace=cadence(
                [t for t in ends if t >= tracer.t_started_epoch]),
            xplane_bytes=os.path.getsize(path), session_stop_s=tracer.stop_s,
            reduce_and_read_s=time.perf_counter() - t_read, busy_s=red.busy_s,
            window_s=red.window_s, spans=len(sink.spans),
            dropped_after_s=None if red.dropped_from_ns is None
            else (red.dropped_from_ns - red.t0_ns) / 1e9,
            whole_executions={n: list(v) for p in red.planes
                              for n, v in p.modules.items()},
            executions={n: v for p in red.planes for n, v in p.executions.items()})
        if args.trace_summary:
            os.makedirs(os.path.dirname(args.trace_summary) or ".", exist_ok=True)
            with open(args.trace_summary, "w") as f:
                json.dump(trace_reduce.summarize(path), f)
        else:  # a trace is tens of megabytes: kept only for a look by hand
            shutil.rmtree(tracer.dir, ignore_errors=True)

    if args.rehearse:
        say(phase="rehearsal", note="NOT a chip run: no metric is reported",
            would_be_correct=is_correct,
            host_clock_values={k: v["value"] for k, v in metrics.items()})
        metrics, is_correct, breakdown = {}, False, None
    result = {
        "correct": is_correct,
        "attempted": len(sample),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    # each number compared beside its limit, last in the line
    result["compared"] = {c.name: [c.value, c.limit] for c in checks}
    return result, checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: no TPU needed, cut sizes, no metric, "
                         "correct false")
    ap.add_argument("--slice-seconds", type=float, nargs="+", default=[10.0],
                    help="the `window` line's `slices`: the window cut into "
                         "pieces this long (10: `slices`; others: "
                         "`slices_<n>s`), for a long run read as many windows")
    ap.add_argument("--benchmark", default="",
                    help="another BENCHMARK.json (tests: a cell that is not "
                         "in the committed one)")
    ap.add_argument("--trace-summary", default="",
                    help="traced run: also write what the trace holds (planes, "
                         "lines, top names) to this file, and keep the trace")
    args = ap.parse_args()
    try:
        result, checks = run(args)
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"run failed, no result: {type(e).__name__}: {e}", flush=True)
        return 1
    for c in checks:  # each number beside its limit, last on stderr too
        print(c.line(), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
