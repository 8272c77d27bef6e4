#!/usr/bin/env python3
"""Chip smoke: the served BN254 verify path on one TPU v5e, end to end.

    python chip_smoke.py            # one chip: two range classes + dense
    python chip_smoke.py --chips 4  # four pinned engines behind one service

One process, the entry points a user calls, full width: a seeded 4096-key
BN254 registry, `BN254Device(batch_size=128)` with the shipping defaults
(cios field backend, per-candidate check), `BatchVerifierService(device,
fallback=None)`, and a few launch groups through `service.verify(...)`:

  * range class — contiguous partitioner level ranges with 0-8 offline
    holes (the prefix-table kernel, miss_k = 8);
  * range64 class — the same ranges with 9-64 (miss_k = 64);
  * dense class — scattered signer sets with more holes in the hull than
    the widest range patch (n // 4; the masked registry tree-sum kernel);

each with ONE forged candidate that must come back False while the rest
come back True, and every verdict compared with the host reference
(`BN254Constructor.batch_verify`, models/bn254.py) on the same requests.
Only the kernel classes that are driven get compiled (no `warmup()`), the
three of them side by side.

`--chips 4` runs the fleet plane instead and nothing else: four engines,
one pinned to each chip (`parallel/plane.py scheme_plane`: one prefix table
and one traced, compiled program a class for the plane, copied and loaded
chip to chip), behind the same service, range class only; `done` says how
each chip came by its executables (`programs`).

Fails — non-zero exit, `"ok": false` on the last line — when the platform
is not `tpu`, the Pallas field kernel is off, the native host library did
not build, any verdict differs from the host's, any launch failed over or
was retried, or any phase raised. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import asyncio
from concurrent.futures import ThreadPoolExecutor
import json
import random
import sys
import threading
import time

N_KEYS = 4096
LANES = 128
SEED = 24
MSG = b"handel-tpu chip smoke"
STEADY_ROUNDS = 3


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


class CompileMeter:
    """Sums JAX's own trace / lowering / backend-compile durations and the
    persistent-cache hits and misses between two `take()` calls, kept per
    thread (JAX reports them from the thread that compiles) so classes
    compiled side by side are told apart."""

    _DUR = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }
    _EVT = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring as mon

        self._acc: dict[int, dict[str, float]] = {}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _add(self, key, amount) -> None:
        if key:
            acc = self._acc.setdefault(threading.get_ident(), {})
            acc[key] = acc.get(key, 0) + amount

    def _on_duration(self, event, duration, **_):
        self._add(self._DUR.get(event), duration)

    def _on_event(self, event, **_):
        self._add(self._EVT.get(event), 1)

    def take(self, thread: int | None = None) -> dict:
        """Totals since the last take: of one thread, or of all of them."""
        taken = (
            [self._acc.pop(thread, {})] if thread is not None
            else [self._acc.pop(t) for t in list(self._acc)]
        )
        return {
            k: sum(acc.get(k, 0) for acc in taken)
            for k in (*self._DUR.values(), *self._EVT.values())
        }


def build_registry(rng: random.Random):
    """Seeded keypairs: secret scalars + G2 public keys through the native
    batch scalar mul (a pure-Python fallback would take minutes here, so a
    missing native library is a failure, not a slow path)."""
    from handel_tpu import native as nat
    from handel_tpu.models.bn254 import BN254PublicKey
    from handel_tpu.ops import bn254_ref as bn

    t0 = time.perf_counter()
    if nat.load() is None:
        raise SmokeFailure(
            "handel_tpu.native.load() returned None: libbn254.so did not "
            "build from native/bn254.cc (g++ missing or failed)"
        )
    t1 = time.perf_counter()
    sks = [rng.randrange(1, bn.R) for _ in range(N_KEYS)]
    pts = nat.g2_mul_batch([bn.G2_GEN] * N_KEYS, sks)
    say(phase="keygen", keys=N_KEYS, native_loaded=True,
        native_load_s=t1 - t0, keygen_s=time.perf_counter() - t1)
    return sks, [BN254PublicKey(p) for p in pts]


def _group(rng, sks, signer_sets):
    """(bitset, aggregate signature) requests for the signer sets, with one
    seeded candidate forged: a well-formed G1 point that signs nothing."""
    from handel_tpu import native as nat
    from handel_tpu.core.bitset import BitSet
    from handel_tpu.models.bn254 import BN254Signature, hash_to_g1
    from handel_tpu.ops import bn254_ref as bn

    forged = rng.randrange(len(signer_sets))
    agg = [sum(sks[i] for i in s) % bn.R for s in signer_sets]
    agg[forged] = (agg[forged] + 1) % bn.R
    sigs = nat.g1_mul_batch([hash_to_g1(MSG)] * len(agg), agg)
    reqs = []
    for s, pt in zip(signer_sets, sigs):
        bs = BitSet(N_KEYS)
        for i in s:
            bs.set(i, True)
        reqs.append((bs, BN254Signature(pt)))
    return reqs, forged


def range_group(rng, sks, holes=(0, 8)):
    """LANES candidates shaped like Handel traffic: an aligned level range
    of the binomial partitioner minus `holes[0]`..`holes[1]` offline members
    (0-8: the `range8` launch class; 9-64: `range64`). A range's first and
    last member always sign, so its hull holes are the members drawn."""
    sizes = [N_KEYS >> l for l in range(1, 7)]  # 64 .. 2048 of 4096 signers
    sizes = [size for size in sizes if size - 2 >= holes[0]]
    sets = []
    for _ in range(LANES):
        size = rng.choice(sizes)
        lo = rng.randrange(N_KEYS // size) * size
        gone = set(rng.sample(
            range(lo + 1, lo + size - 1),
            rng.randrange(holes[0], min(holes[1], size - 2) + 1),
        ))
        sets.append([i for i in range(lo, lo + size) if i not in gone])
    return _group(rng, sks, sets)


def dense_group(rng, sks):
    """LANES candidates with scattered signer sets: about half the registry
    each, so every hull has far more holes (about n / 2) than the widest
    range patch (n // 4)."""
    sets = [
        [i for i in range(N_KEYS) if rng.random() < 0.5] for _ in range(LANES)
    ]
    return _group(rng, sks, sets)


def check_group(name, got, want, forged) -> None:
    """Device verdicts equal the host's, and the host's are the expected
    pattern (forged candidate False, everything else True)."""
    expect = [j != forged for j in range(len(want))]
    if want != expect:
        raise SmokeFailure(f"{name}: host reference disagrees with the "
                           f"construction (forged lane {forged})")
    if got != want:
        bad = [j for j, (g, w) in enumerate(zip(got, want)) if g != w]
        raise SmokeFailure(f"{name}: device verdicts differ from the host "
                           f"reference at lanes {bad}")


def check_counters(service, launches: int) -> dict:
    v = service.values()
    counters = {
        "launches": int(v["verifierLaunches"]),
        "candidates": int(v["verifierCandidates"]),
        "failoverBatches": int(v["failoverBatches"]),
        "deviceRetries": int(v["deviceRetryCt"]),
    }
    say(phase="counters", **counters)
    if counters["failoverBatches"] or counters["deviceRetries"]:
        raise SmokeFailure(f"launches failed over or were retried: {counters}")
    if counters["launches"] != launches:
        raise SmokeFailure(
            f"expected {launches} device launches, service counted "
            f"{counters['launches']} (verdicts served from elsewhere?)"
        )
    return counters


def compile_classes(engine, groups, host, meter) -> None:
    """Compile exactly the launch classes the smoke drives — one real
    candidate of each through the engine's own dispatch/fetch — side by
    side: each class is minutes of single-threaded XLA, and two of them in
    a row would eat most of the smoke's time limit. The engine has one
    dispatcher at a time (its staging buffers rotate), so a class is handed
    over only once the one before is past its pack; tracing and compiling,
    which is where the minutes go, then overlap."""

    def warm(name):
        t0 = time.perf_counter()
        got = engine.fetch(engine.dispatch(MSG, groups[name][0][:1]))
        return got, time.perf_counter() - t0, threading.get_ident()

    with ThreadPoolExecutor(len(groups)) as pool:
        futs = {}
        for packed, name in enumerate(groups):
            futs[name] = pool.submit(warm, name)
            while engine.host_pack_launches <= packed and not futs[name].done():
                time.sleep(0.01)
        for name, fut in futs.items():
            got, wall, thread = fut.result()
            if got != host[name][:1]:
                raise SmokeFailure(f"{name}: first verdict differs from host")
            say(phase=f"compile_{name}", seconds=wall, **meter.take(thread))


async def serve_one_chip(service, pubkeys, groups, host, meter) -> int:
    """Each class: the whole group once, then the same candidates again
    under fresh session tags (a new dedup scope, so every round is a real
    launch) for the steady wall. Nothing compiles here."""
    launches = 0
    for name, (reqs, forged) in groups.items():
        t0 = time.perf_counter()
        got = await service.verify(MSG, pubkeys, reqs, session=f"{name}-first")
        cold = time.perf_counter() - t0
        check_group(f"{name} first", got, host[name], forged)
        steady = []
        for r in range(STEADY_ROUNDS):
            t0 = time.perf_counter()
            got = await service.verify(
                MSG, pubkeys, reqs, session=f"{name}-steady{r}"
            )
            steady.append(time.perf_counter() - t0)
            check_group(f"{name} steady{r}", got, host[name], forged)
        launches += 1 + STEADY_ROUNDS
        say(phase=f"serve_{name}", candidates=len(reqs), forged_lane=forged,
            forged_rejected=True, verdicts_equal_host=True,
            first_call_s=cold, steady_launch_wall_s=steady, **meter.take())
    return launches


async def serve_fleet(service, pubkeys, groups, host, meter) -> int:
    """All groups in flight at once, twice: the first wave compiles the
    class on the chip that asks first and loads it on the others, the
    second is the steady fleet wall."""
    launches = 0
    for wave in ("cold", "steady"):
        t0 = time.perf_counter()
        got = await asyncio.gather(*(
            service.verify(MSG, pubkeys, reqs, session=f"{name}-{wave}")
            for name, (reqs, _) in groups.items()
        ))
        wall = time.perf_counter() - t0
        for (name, (_, forged)), g in zip(groups.items(), got):
            check_group(f"{name} {wave}", g, host[name], forged)
        launches += len(groups)
        say(phase=f"serve_fleet_{wave}", groups=len(groups),
            candidates=sum(len(r) for r, _ in groups.values()),
            forged_rejected=True, verdicts_equal_host=True, wall_s=wall,
            lane_launches=[l.launches for l in service.plane.lanes],
            **meter.take())
    return launches


def host_reference(pubkeys, groups) -> dict:
    from handel_tpu.models.bn254 import BN254Constructor

    t0 = time.perf_counter()
    cons = BN254Constructor()
    host = {
        name: cons.batch_verify(MSG, pubkeys, reqs)
        for name, (reqs, _) in groups.items()
    }
    say(phase="host_reference", groups=len(groups),
        seconds=time.perf_counter() - t0)
    return host


def run(chips: int) -> dict:
    from handel_tpu.utils.jaxenv import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    say(phase="device", compile_cache_dir=cache_dir, **device)
    if device["platform"] != "tpu":
        raise SmokeFailure(
            f"JAX found no TPU (platform {device['platform']!r}): this "
            "smoke measures nothing on another backend"
        )
    if len(devs) != chips:
        raise SmokeFailure(f"asked for {chips} chip(s), JAX sees {len(devs)}")
    out = drive(chips, CompileMeter())
    if not out.pop("use_pallas"):
        raise SmokeFailure("curves.F.use_pallas is false on the TPU")
    return {"device": device, **out}


def drive(chips: int, meter: CompileMeter) -> dict:
    """Everything after device selection. `run` is the only caller that
    has checked for the chip; a rehearsal can call this on (virtual) CPU
    devices with N_KEYS/LANES cut down."""
    import jax

    from handel_tpu.models.bn254_jax import BN254Device
    from handel_tpu.ops.fp import default_pow_window
    from handel_tpu.parallel.batch_verifier import BatchVerifierService
    from handel_tpu.parallel.plane import scheme_plane

    rng = random.Random(SEED)
    sks, pubkeys = build_registry(rng)

    t0 = time.perf_counter()
    if chips == 1:
        engines = [BN254Device(pubkeys, batch_size=LANES)]
        target = engines[0]
    else:
        target = scheme_plane(pubkeys, devices=chips, batch_size=LANES,
                              scheme="bn254-jax")
        engines = [lane.engine for lane in target.lanes]
    F = engines[0].curves.F
    say(phase="engines", engines=len(engines), registry=engines[0].n,
        lanes=LANES, fp_backend=F.backend, use_pallas=F.use_pallas,
        pow_window=default_pow_window(), batch_check=engines[0].batch_check,
        seconds=time.perf_counter() - t0)
    placed = [sorted(d.id for d in e._reg_x[0].devices()) for e in engines]
    if len({tuple(p) for p in placed}) != len(engines):
        raise SmokeFailure(f"engine registries share a device: {placed}")

    # the prefix table is built on the first range dispatch; build it here
    # so its scan is timed apart from the launch classes (a plane scans on
    # its first chip and copies the table to the others)
    t0 = time.perf_counter()
    for e in engines:
        jax.block_until_ready(e._prefix)
    tables = [sorted(d.id for d in e._prefix[2].devices()) for e in engines]
    if tables != placed:
        raise SmokeFailure(f"prefix tables on {tables}, registries on {placed}")
    say(phase="prefix_table", registry_devices=placed,
        seconds=time.perf_counter() - t0, **meter.take())

    if chips == 1:
        groups = {"range": range_group(rng, sks),
                  "range64": range_group(rng, sks, holes=(9, 64)),
                  "dense": dense_group(rng, sks)}
        host = host_reference(pubkeys, groups)
        compile_classes(target, groups, host, meter)
        serve_groups = serve_one_chip
    else:
        groups = {f"range{i}": range_group(rng, sks) for i in range(2 * chips)}
        host = host_reference(pubkeys, groups)
        serve_groups = serve_fleet
    service = BatchVerifierService(target, fallback=None)

    async def serve():
        try:
            return await serve_groups(service, pubkeys, groups, host, meter)
        finally:
            service.stop()

    launches = asyncio.run(serve())
    counters = check_counters(service, launches)
    lane_launches = [lane.launches for lane in service.plane.lanes]
    if min(lane_launches) < 1:
        raise SmokeFailure(f"a lane never launched: {lane_launches}")
    v = service.values()
    return {"counters": counters, "lane_launches": lane_launches,
            "programs": {"compiles": v["programCompiles"],
                         "loads": v["programLoads"]},
            "use_pallas": F.use_pallas}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the fleet plane on four chips, and nothing else")
    args = ap.parse_args()
    t0 = time.perf_counter()
    try:
        out = run(args.chips)
    except BaseException as e:
        import traceback

        traceback.print_exc()
        say(phase="failed", seconds=time.perf_counter() - t0)
        say(ok=False, error=f"{type(e).__name__}: {e}")
        return 1
    say(phase="done", seconds=time.perf_counter() - t0, **out)
    say(ok=True, device=out["device"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
