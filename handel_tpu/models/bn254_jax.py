"""BLS-over-BN254 with verification on the JAX/TPU path.

This is the device Constructor the project exists for: it replaces the serial
verify loop of the reference (`verifySignature`, processing.go:342-368 —
aggregate-pubkey loop + `bn256.Pair` at bn256/cf/bn256.go:86-98) with ONE
batched launch per candidate batch:

  1. aggregate public keys = masked tree-sum in the KEY GROUP over the
     device-resident registry array (ops/curve.py `masked_sum`; the
     reference's per-signature Combine loop at processing.go:355-361),
  2. batched product-of-pairings check, for every candidate j,
     e(H(m), X_j) * e(-S_j, B2) == 1   (keys in G2, signatures in G1) or
     e(X_j, H(m)) * e(-B1, S_j) == 1   (keys in G1, signatures in G2)
     with one shared final exponentiation (ops/pairing.py `pairing_check`;
     the reference's per-signature two-pairing compare, bn256/go/bn256.go:82-94).

Which of the curve family's two groups holds the keys is the engine's GROUP
BINDING (`BN254Device.key_group`), a property of the scheme's name as the
curve is; signatures and H(m) live in the other group.

Keys/signatures/wire formats are the host objects from models/bn254.py
(cloudflare-compatible marshal); only verification moves on device. Candidate
batches are padded to a fixed `batch_size` so the jit executable is reused
across calls.
"""

from __future__ import annotations

import io
import random
import threading
import time
from collections import Counter, namedtuple
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.core import trace_state_clean

from handel_tpu.core.bitset import BitSet
from handel_tpu.core.logging import DEFAULT_LOGGER
from handel_tpu.core.trace import LAUNCH_CLASSES, StageClock
from handel_tpu.models import rlc
from handel_tpu.models.bn254 import (
    BN254Constructor,
    BN254PublicKey,
    BN254Scheme,
    BN254Signature,
    hash_to_g1,
)
from handel_tpu.utils.breaker import CircuitBreaker
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops.curve import BN254Curves
from handel_tpu.ops.fp import MUL_ROW_SUBLANES, MUL_STEP_LANES, device_platform
from handel_tpu.ops.pairing import BN254Pairing

# Device-input arrays for one launch, as the packer hands them to dispatch:
# kind selects the kernel family ("range" = prefix-table path with a miss_k-
# wide hole patch, miss_k one of the engine's `patch_widths`; "dense" =
# masked registry sum); sig_* are the signature group's packed coordinates
# (a limb array in G1, a pair of them in G2); valid masks the real lanes.
# Array fields not used by `kind` are None. `words` is the (C, W) uint64
# bitset-word matrix — for a dense plan
# it IS the device-transfer source (the kernel unpacks the candidate masks
# on device; no host-side (n, C) mask is ever materialized: `mask` stays
# None; the tests' loop oracle fills it). Plans from `_pack_requests` view
# ROTATED staging buffers (see `_StagingSet`): a plan stays valid until the
# staging rotation wraps back onto its set — with the default two sets, the
# second-next `_pack_requests` call invalidates it.
LaunchPlan = namedtuple(
    "LaunchPlan",
    "kind miss_k lo hi miss_idx miss_ok words mask sig_x sig_y valid",
)


def _named(fn, name: str):
    """`fn` under a stable `__name__`. jit names the compiled program after
    the function it was given ("jit_<name>" on the profiler's "XLA Modules"
    line); a `partial` or a bound method has none of its own to set, and
    reads as `jit__unknown`."""

    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = name
    return call


class PlanePrograms:
    """The launch programs the pinned engines of ONE plane share
    (parallel/plane.py `scheme_plane`): its lanes serve one registry at one
    shape, so whatever does not depend on the chip is done once.

    `jit(key, build)` keeps one `jax.jit` object a launch class: the class is
    TRACED once for the plane, whichever lane asks first (the bank and the
    prefix table are arguments, never closures). `run` executes it on a
    lane's chip through an executable held per (class, shapes, chip): the
    first chip to ask lowers and compiles it (or loads it from the compile
    cache), and it is serialised; every other chip LOADS those bytes with
    its own device assignment. JAX's compile cache cannot do that: its key
    carries the device assignment off the GPU (jax/_src/cache_key.py), so
    chip k would miss on what chip 0 wrote. Where the runtime refuses the
    bytes on another chip, that chip compiles for itself (what every lane
    did before there was a plane) and `compiles` says so.

    `compiles` / `loads` count executables by how a chip came to hold them.
    A program is serialised when a SECOND chip asks for it (a plane of one
    never pays for that), and the bytes stay on the host for a lane
    attached later. A program's trace closes over the engine that asked
    first, which so lives as long as the plane's programs do.
    """

    def __init__(self):
        self._jitted: dict = {}
        self._loaded: dict = {}       # (key, shapes, chip) -> jax.stages.Compiled
        # (key, shapes) -> the first chip's executable, then (once a second
        # chip asks) its bytes and trees, or None where it cannot travel
        self._portable: dict = {}
        self._lock = threading.Lock()
        self.compiles: Counter = Counter()  # by class key
        self.loads: Counter = Counter()

    def jit(self, key, build):
        if key not in self._jitted:
            self._jitted.setdefault(key, build())
        return self._jitted[key]

    def run(self, key, device, *args):
        shapes = tuple(a.shape for a in jax.tree_util.tree_leaves(args))
        exe = self._loaded.get((key, shapes, device))
        if exe is None:  # one dispatcher a lane: no two ask for one chip
            exe = self._loaded[key, shapes, device] = self._executable(
                key, shapes, device, args)
        return exe(*args)

    def _executable(self, key, shapes, device, args):
        from jax.experimental import serialize_executable as se

        # one chip at a time: the others wait for the first one's bytes
        # (and XLA:CPU loses functions of executables loaded side by side)
        with self._lock:
            if (key, shapes) not in self._portable:
                exe = self._portable[key, shapes] = self._compile(key, args)
                return exe
            first = self._portable[key, shapes]
            try:
                if isinstance(first, jax.stages.Compiled):
                    first = self._portable[key, shapes] = se.serialize(first)
                if first is not None:
                    exe = _load_on(se, first, device)
                    # a runtime may load what it cannot run: find out here,
                    # on copies made through the host (a launch's own inputs
                    # are donated, and a device copy would compile)
                    jax.block_until_ready(exe(*jax.device_put(
                        _tree(np.asarray, args), device)))
                    self.loads[key] += 1
                    return exe
            except Exception as e:  # this runtime binds a program to a chip
                DEFAULT_LOGGER.warn("plane_program_not_portable", key, e)
                self._portable[key, shapes] = None
            return self._compile(key, args)

    def _compile(self, key, args):
        exe = self._jitted[key].lower(*args).compile()
        self.compiles[key] += 1
        return exe


def _load_on(se, blob, device):
    """`serialize_executable.deserialize_and_load` onto ANOTHER chip than the
    one that compiled: that function looks the pickled devices up by id, so
    it only loads where it compiled. Here every device of the one-chip
    program reads as `device`, and the runtime is handed that chip's
    device assignment with the bytes."""
    from jax._src import compiler

    serialized, in_tree, out_tree = blob

    class Onto(se._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] == "device":
                return device
            if pid[0] == "exec":
                return self.backend.deserialize_executable(
                    pid[1], executable_devices=self.execution_devices,
                    compile_options=compiler.get_compile_options(
                        num_replicas=1, num_partitions=1,
                        device_assignment=np.array([[device.id]]),
                    ),
                )
            return super().persistent_load(pid)

    unloaded, args_info, no_kwargs = Onto(
        io.BytesIO(serialized), device.client, [device]).load()
    return jax.stages.Compiled(
        unloaded.load(), [], in_tree.unflatten(args_info), out_tree,
        no_kwargs=no_kwargs,
    )


class _StagingSet:
    """One pre-allocated set of host staging buffers for the launch packer.

    The device owns `stage_sets` of these (default two) and rotates per
    `_pack_requests` call — double buffering, so the arrays a still-in-flight
    launch's `jax.device_put` handoff may alias (jax's CPU client zero-copy-
    aliases some dtypes) are never overwritten while that launch can still
    read them. `fence` holds the verdict array of the last launch that used
    this set: before the rotation reuses the set, the packer blocks on it —
    a completed launch has consumed (or device-copied) every input, so the
    wait resolves instantly in steady state and only throttles a pipeline
    that outran `stage_sets` launches of buffering (backpressure, never
    corruption). Single-dispatcher contract: one thread packs/dispatches
    (BatchVerifierService's collector, or a caller's own loop).
    """

    __slots__ = ("words", "valid", "lo", "hi", "miss", "miss_ok",
                 "sig_x", "sig_y", "fence")

    def __init__(self, n: int, C: int, miss_cap: int, nlimbs: int,
                 sig_cols: int = 1):
        self.words = np.zeros((C, (n + 63) // 64), np.uint64)
        self.valid = np.zeros((C,), bool)
        self.lo = np.zeros((C,), np.int32)
        self.hi = np.zeros((C,), np.int32)
        self.miss = np.zeros((miss_cap, C), np.int64)
        self.miss_ok = np.zeros((miss_cap, C), bool)
        # a signature coordinate is `sig_cols` base-field columns: one limb
        # array in G1, a pair of them in G2
        col = lambda: np.zeros((nlimbs, C), np.uint32)
        coord = lambda: col() if sig_cols == 1 else tuple(
            col() for _ in range(sig_cols))
        self.sig_x, self.sig_y = coord(), coord()
        self.fence = None


def _cols(elem) -> tuple:
    """The base-field columns of one packed coordinate (Fp: the array
    itself; Fp2: its pair)."""
    return elem if isinstance(elem, tuple) else (elem,)


# per-column map over packed coordinates, whichever group they belong to
_tree = jax.tree_util.tree_map


class _WarmupSig:
    """Minimal signature stand-in for warmup launches (only `.point` is
    read by the packer); verdicts are discarded, so no real signing."""

    __slots__ = ("point",)

    def __init__(self, point):
        self.point = point


class BN254Device:
    """Device-side verification engine bound to one registry.

    Holds the registry's public keys as dense (nlimbs, N) key-group
    coordinate arrays uploaded once (SURVEY.md §2.1 identity row: "registry
    pubkeys additionally uploaded once to device memory as a dense G2
    array").

    Curve-family and group bindings are class attributes so the BLS12-381
    devices (models/bls12_381_jax.py) reuse the whole launch machinery.
    """

    ref = bn  # scalar-oracle module of the curve family
    Curves = BN254Curves
    Pairing = BN254Pairing
    # group binding: registry keys, prefix table, hull gather, hole patch
    # and dense mask live in G<key_group> (`self.kg`); staging, the cached
    # H(m) and `combine_batch` in the other group (`self.sg`). A coordinate
    # is one limb array in G1 and an Fp2 pair of them in G2, so everything
    # between the packer and the pairing maps over a coordinate's columns.
    key_group = 2
    _hash_to_sig_group = staticmethod(hash_to_g1)

    def __init__(
        self,
        registry_pubkeys: Sequence[BN254PublicKey],
        batch_size: int = 16,
        curves: BN254Curves | None = None,
        mesh_devices: int = 1,
        jax_device=None,
        rns_resident: bool | None = None,
        batch_check: str = "per_candidate",
        rlc_rng: random.Random | None = None,
        plane_of: "BN254Device | None" = None,
    ):
        # batch_check selects the launch contract: "per_candidate" = one
        # pairing-check lane pair per candidate (2C Miller loops, C final
        # exps); "rlc" = the random-linear-combination combined check
        # (models/rlc.py — M+1 Miller loops, 1 final exp, two MSMs) with
        # bisection fallback down to the per-candidate oracle on failure
        self.batch_check = rlc.validate_batch_check(batch_check)
        # adversary-facing randomness: SystemRandom unless a test injects
        # a seeded stream for reproducible bisection traces
        self._rlc_rng = rlc_rng or random.SystemRandom()
        self.rlc_stats = rlc.RlcStats()
        self.curves = curves or self.Curves()
        g1, g2 = self.curves.g1, self.curves.g2
        self.kg, self.sg = (g2, g1) if self.key_group == 2 else (g1, g2)
        if self.key_group == 1:
            # the RLC launch class, the mesh pipeline and the residue-
            # resident field were written and proven with keys in G2 only
            for option, asked in (
                ('batch_check="rlc"', self.batch_check == "rlc"),
                ("mesh_devices > 1", mesh_devices > 1),
                ('fp_backend="rns"', self.curves.F.backend == "rns"),
            ):
                if asked:
                    raise ValueError(
                        f"{type(self).__name__} keeps keys in G1 and "
                        f"signatures in G2 and does not support {option}: "
                        "that path exists for keys in G2 only"
                    )
        # rns_resident toggles the residue-resident pairing form
        # (ops/pairing.py): None = auto (on exactly for the 'rns' field
        # backend), False forces per-mul CRT, True demands the rns backend
        self.pairing = self.Pairing(self.curves, resident=rns_resident)
        self.batch_size = batch_size
        self.n = len(registry_pubkeys)
        # fleet pinning (parallel/plane.py): when `jax_device` is given,
        # every explicit put — registry commit, staging handoff, cached
        # H(m) — lands COMMITTED to that chip, so jit executes this
        # engine's launches there and K engines fill K chips concurrently.
        # None keeps the historical uncommitted-default placement.
        self.jax_device = jax_device
        self._dput = (
            partial(jax.device_put, device=jax_device)
            if jax_device is not None
            else jax.device_put
        )
        # the registry is committed to the device ONCE, here, and every
        # launch selects from it with on-device gathers (the prefix table
        # below is derived from these arrays and lives on device too) —
        # steady-state launches perform no implicit host→device transfer
        # of registry/prefix data (pinned by tests/test_device_residency.py
        # under jax.transfer_guard)
        # ... once a PLANE: `plane_of` is an engine of the same plane, built
        # from the same keys on another chip. Its bank is copied chip to
        # chip (the keys are converted on the host once), its prefix table
        # likewise when a range launch first asks (`_prefix`), and the two
        # run the same launch programs (`PlanePrograms`). A pinned engine
        # alone is a plane of one.
        if plane_of is not None and (
            type(plane_of) is not type(self) or plane_of.n != self.n
            or plane_of.batch_size != batch_size or jax_device is None
        ):
            raise ValueError(
                "plane_of wants a pinned engine of the same class, registry "
                "size and batch size"
            )
        self._plane_of = plane_of
        self.programs = (
            plane_of.programs if plane_of is not None
            else PlanePrograms() if jax_device is not None else None
        )
        if plane_of is not None:
            self._reg_x, self._reg_y = _tree(
                self._dput, (plane_of._reg_x, plane_of._reg_y))
        else:
            self._reg_x, self._reg_y = self._put_bank(
                registry_pubkeys, "registry")
        # multi-chip plane (SURVEY.md §5.7): registry shards over the mesh
        # for the masked key segment-sum, candidate lanes shard for the
        # pairing check. Same host entry points — `_dispatch_one` routes to
        # a STAGED pipeline of separate executables (sharded sum / range
        # aggregation -> affine epilogue -> sharded pairing check) instead of
        # the single-device monolithic kernels: nesting shard_map regions
        # inside the big jit sends XLA's partitioner over the whole pairing
        # graph, which takes hours on a 1-core host (parallel/sharding.py
        # module docstring has the measurement).
        self.mesh_devices = mesh_devices
        self.mesh = None
        self._sharded_sum = self._sharded_check = None
        self.mesh_launches = 0
        self.mesh_candidates = 0
        if mesh_devices > 1:
            from handel_tpu.parallel.sharding import (
                commit_registry_sharded,
                launch_partition_rules,
                make_mesh,
                make_shard_fns,
                match_partition_rules,
                sharded_masked_sum_g2,
                sharded_pairing_check,
            )

            self.mesh = make_mesh(mesh_devices)
            self._sharded_sum = sharded_masked_sum_g2(
                self.curves, self.mesh, self.n, batch_size
            )
            self._sharded_check = sharded_pairing_check(
                self.pairing, self.mesh, batch_size
            )
            # the mesh counterpart of the single-chip resident registry:
            # pad the coordinate arrays to the device multiple and commit
            # one shard per chip ONCE, here — before this, every dense
            # sharded launch handed the full replicated arrays to
            # `_sharded_sum` and paid a re-shard (all-to-all of the whole
            # registry) per launch
            self._reg_sharded = commit_registry_sharded(
                self.mesh, self._reg_x, self._reg_y, self.n
            )
            # per-launch operand placement by partition rule (the
            # SNIPPETS.md [1][2] rule-matching/shard_fns idiom): the dense
            # candidate mask is pre-padded on the host and device_put in
            # its registry-axis shard_map layout, so `_sharded_sum` sees
            # one shard per chip instead of re-sharding a replicated mask
            # every launch (the same win commit_registry_sharded bought
            # the registry banks)
            self._mesh_pad = (-self.n) % mesh_devices
            self._mesh_put = make_shard_fns(
                self.mesh,
                match_partition_rules(
                    launch_partition_rules(),
                    ["reg_x", "reg_y", "mask", "sig_x", "sig_y", "valid"],
                ),
            )
            self._affine_kernel = jax.jit(self.curves.g2.to_affine)
            self._neg_kernel = jax.jit(self.curves.F.neg)
            T = self.curves.T
            self._b2x = T.f2_pack([self.ref.G2_GEN[0]])
            self._b2y = T.f2_pack([self.ref.G2_GEN[1]])
        # staged-kernel cache: used by every mesh launch and by any caller
        # profiling the aggregation stage standalone on one device
        self._range_agg_kernels: dict[int, callable] = {}
        self._h_cache: dict[bytes, tuple] = {}
        # host-side H(m) limb columns + launch counter for the per-lane-h
        # multi-message path (dispatch_multi)
        self._h_np_cache: dict[bytes, tuple] = {}
        self.multi_msg_launches = 0
        # prefix table: slot i = sum of registry keys [0, i) in affine, with
        # an explicit infinity flag (slot 0). Built lazily on the first
        # range-path dispatch (dense-only users never pay the scan); after
        # that every contiguous candidate costs two gathers + one add.
        self._prefix_cache = None
        # buffer donation: per-launch inputs (staging transfers, never the
        # registry/prefix residents or the cached H(m)) are donated so XLA
        # reuses their device buffers in place instead of allocating fresh
        # ones per launch. Gated off the CPU client, where device buffers
        # can ALIAS the host staging arrays — donating an aliased buffer
        # would let XLA scribble over our staging memory.
        self._donate = device_platform() != "cpu"
        self._kernel = self._program(
            "verify_dense", self._verify_batch, donate=(2, 3, 4, 7))
        self._range_kernels: dict[int, callable] = {}
        self._combine_kernels: dict[int, callable] = {}
        # RLC launch-class kernels: the MSM/aggregation stage keyed by
        # (kind, miss_k, n_groups) and the G+1-lane pairing tail keyed by
        # n_groups (n_groups quantized to powers of two, same reasoning as
        # the miss_k classes: each tail variant is a pairing-graph compile)
        self._rlc_msm_kernels: dict[tuple, callable] = {}
        self._rlc_check_kernels: dict[int, callable] = {}
        # rotated zero-copy staging (double-buffered by default): bitset
        # uint64 words land directly in these pinned arrays, which are the
        # device-transfer source — ONE explicit jax.device_put per array in
        # `_stage_plan`, no per-launch snapshot copies. See _StagingSet for
        # the rotation/fence contract.
        self.stage_sets = 2
        self._new_staging()
        # host cost per launch, by stage (core/trace.py StageClock; monitor
        # plane via BatchVerifierService.values): fence_wait + pack build
        # the launch plan in staging (`host_pack_ms`), stage + enqueue are
        # the device handoff and the async kernel call that follow
        # (`host_dispatch_ms`), fetch_wait + fetch_copy pull the verdicts.
        # `_launch` and `_pull` are the only places a stage is timed.
        self.stage_clock = StageClock()
        # ... beside them the launch counts: per stage side, per launch
        # class (`_count_class`), how full the wide class's patch runs
        # (slots = wide x valid lanes, holes = the slots that carry a hole),
        # and the Miller loop's steps with those whose addition executes
        self.reset_host_counters()
        # launches are numbered per engine, from 0, in dispatch order; the
        # handle carries the number to `fetch`, and the service reads it
        # through `launch_seq`, so a launch's stages, spans and (dispatch
        # order = execution order) its run on the device are one chain
        self._next_seq = 0
        # epoch-based registry rotation (lifecycle/epoch.py): a second
        # device-resident bank is staged via `stage_registry` while this
        # one keeps serving; `activate_staged` is the pointer flip between
        # launches. `epoch` counts flips — 0 is the construction-time set.
        self.epoch = 0
        self._staged: dict | None = None
        self.registry_stagings = 0
        self.registry_staged_ms = 0.0

    @property
    def field_limbs(self) -> int:
        """16-bit limbs of the base field (the `fieldLimbs` gauge)."""
        return self.curves.F.nlimbs

    @property
    def fp_mul_step_lanes(self) -> int:
        """The most lanes a pass of the multiplication kernel's body
        computes (the `fpMulStepLanes` gauge)."""
        return MUL_STEP_LANES

    @property
    def fp_mul_row_sublanes(self) -> int:
        """Sublanes of a vector register a limb row of that kernel fills at
        its widest calls (the `fpMulRowSublanes` gauge)."""
        return MUL_ROW_SUBLANES

    def _put_bank(self, pubkeys, what: str):
        """One registry bank on the device: the keys' affine coordinates as
        key-group columns (x, y)."""
        pts = [pk.point for pk in pubkeys]
        if any(p is None for p in pts):
            raise ValueError(
                f"{what} public keys must be valid G{self.key_group} points"
            )
        pack = self.kg.ops.pack
        return (
            self._dput(pack([p[0] for p in pts])),
            self._dput(pack([p[1] for p in pts])),
        )

    def _new_staging(self) -> None:
        """Fresh staging sets for the current registry size."""
        self._stage = [
            _StagingSet(
                self.n, self.batch_size, self.patch_widths[-1],
                self.curves.F.nlimbs, self.sg.ops.COLS,
            )
            for _ in range(self.stage_sets)
        ]
        self._stage_idx = 0

    @property
    def host_pack_ms(self) -> float:
        ms = self.stage_clock.ms
        return ms["fence_wait"] + ms["pack"]

    @property
    def host_dispatch_ms(self) -> float:
        ms = self.stage_clock.ms
        return ms["stage"] + ms["enqueue"]

    def _program(self, name: str, fn, donate=()):
        """`fn` as the launch program `name` (jit names the executable
        `jit_<name>`), with the per-launch inputs `donate` handed over where
        the platform allows: this engine's own `jax.jit`, or, for an engine
        pinned to a chip, its plane's one program of that name run on this
        chip (`PlanePrograms`)."""
        build = lambda: jax.jit(
            _named(fn, name), donate_argnums=donate if self._donate else ())
        if self.programs is None:
            return build()
        self.programs.jit(name, build)
        return partial(self.programs.run, name, self.jax_device)

    @property
    def _prefix(self):
        if self._prefix_cache is None:
            # never build under an active trace — the result would cache
            # tracers (see _range_kernel, which pre-materializes on the
            # host)
            if not trace_state_clean():
                raise RuntimeError("prefix table must be built outside jit")
            src = self._plane_of
            if src is not None and src.epoch == self.epoch == 0:
                # both still serve the bank they were built with, which is
                # one bank: the scan runs on ONE chip of the plane
                self._prefix_cache = _tree(self._dput, src._prefix)
            else:
                self._prefix_cache = self._build_prefix()
        return self._prefix_cache

    def _build_prefix(self, reg_x=None, reg_y=None):
        """Prefix table over a registry bank (default: the active one).
        `stage_registry` passes the STAGED bank so the scan runs off the
        launch critical path."""
        x, y, inf = self._prefix_table_kernel()(
            self._reg_x if reg_x is None else reg_x,
            self._reg_y if reg_y is None else reg_y,
        )
        pad = lambda a: jnp.pad(a, ((0, 0), (1, 0)))  # exclusive: slot 0 = O
        return (
            _tree(pad, x),
            _tree(pad, y),
            jnp.pad(inf, (1, 0), constant_values=True),
        )

    def _prefix_table_kernel(self):
        """One executable for the whole scan + batch affine convert."""
        kg = self.kg

        def prefix_table(reg_x, reg_y):
            P = kg.from_affine(reg_x, reg_y)
            pref = kg.prefix_scan(P)  # inclusive prefix sums, projective
            return kg.to_affine(pref)

        return jax.jit(prefix_table)

    # -- epoch-based registry rotation (lifecycle/epoch.py) ----------------

    def stage_registry(
        self, registry_pubkeys: Sequence[BN254PublicKey],
        build_prefix: bool = True,
    ) -> int:
        """Stage the NEXT validator set as a second device-resident bank
        while the active one keeps serving launches. Everything expensive —
        the host limb pack, the device_put, the prefix-table scan — happens
        here, off the launch critical path; the later `activate_staged` is
        a pointer flip between launches. Re-staging before activation
        replaces the pending bank (last staging wins). Returns the staged
        registry size."""
        t0 = time.perf_counter()
        reg_x, reg_y = self._put_bank(registry_pubkeys, "staged registry")
        prefix = None
        if build_prefix:
            prefix = self._build_prefix(reg_x, reg_y)
            # materialize NOW: the flip must never pay the scan
            jax.block_until_ready(prefix[2])
        else:
            jax.block_until_ready(reg_y)
        self._staged = {
            "reg_x": reg_x, "reg_y": reg_y, "n": len(registry_pubkeys),
            "prefix": prefix,
        }
        self.registry_stagings += 1
        self.registry_staged_ms += (time.perf_counter() - t0) * 1e3
        return len(registry_pubkeys)

    def activate_staged(self) -> int:
        """Flip the staged bank live — the caller quiesces launches around
        this (lifecycle/epoch.py EpochManager.commit). Cheap by
        construction: pointer swaps, plus a staging-buffer realloc only
        when the registry size changed. Returns the new epoch."""
        st = self._staged
        if st is None:
            raise RuntimeError("no staged registry: call stage_registry first")
        if self.mesh is not None:
            if st["n"] != self.n:
                # the sharded sum/check executables are specialized to the
                # construction-time registry width; resizing would need a
                # rebuild of the whole staged pipeline
                raise RuntimeError(
                    "mesh-sharded registry rotation requires an equal-size "
                    f"validator set (active {self.n}, staged {st['n']})"
                )
            from handel_tpu.parallel.sharding import commit_registry_sharded

            self._reg_sharded = commit_registry_sharded(
                self.mesh, st["reg_x"], st["reg_y"], st["n"]
            )
        self._reg_x, self._reg_y = st["reg_x"], st["reg_y"]
        self._prefix_cache = st["prefix"]
        if st["n"] != self.n:
            self.n = st["n"]
            self._new_staging()
        self._staged = None
        self.epoch += 1
        return self.epoch

    # -- the jitted batch kernels ------------------------------------------

    def _pairing_tail(self, agg, sig_x, sig_y, h_x, h_y, valid):
        """Shared epilogue: affine-convert the aggregates and run the batched
        product-of-pairings check over 2C lanes. The key side's lanes are
        [aggregate X_j, generator B], the signature side's [H(m), S_j], and
        the G1 side's second half is negated:
        e(H, X_j) * e(-S_j, B2) == 1 with keys in G2,
        e(X_j, H) * e(-B1, S_j) == 1 with keys in G1."""
        C = self.batch_size
        kg = self.kg
        neg = self.curves.F.neg
        with jax.named_scope("to_affine"):
            agg_inf = kg.is_infinity(agg)
            ax, ay, _ = kg.to_affine(agg)

        wide = lambda c, like: _tree(
            lambda a, l: jnp.broadcast_to(a, l.shape), c, like)
        cat = lambda c, d: _tree(
            lambda a, b: jnp.concatenate([a, b], axis=1), c, d)
        gx = wide(kg.ops.pack([kg.gen[0]]), ax)
        gy = wide(kg.ops.pack([kg.gen[1]]), ay)
        if self.key_group == 2:
            sig_y = neg(sig_y)
        else:
            gy = neg(gy)
        ok_lane = valid & ~agg_inf
        sig_side = (cat(wide(h_x, sig_x), sig_x), cat(wide(h_y, sig_y), sig_y))
        key_side = (cat(ax, gx), cat(ay, gy))
        p, q = (
            (sig_side, key_side) if self.key_group == 2
            else (key_side, sig_side)
        )
        lane_mask = jnp.concatenate([ok_lane, ok_lane])
        checks = self.pairing.pairing_check(p, q, lane_mask, C)
        return checks & ok_lane

    def _unpack_words(self, words32, valid, n: int):
        """(C, 2W) uint32 bitset words -> (N*C,) block-major candidate mask,
        entirely on device: a gather + shift per registry index replaces the
        host-side (N, C) mask materialization the dense path used to stage
        and transfer (~N*C bytes/launch; the words are N/8 bytes)."""
        idx = jnp.arange(n)
        w = words32[:, idx // 32]  # (C, N) on-device gather
        bits = ((w >> (idx % 32).astype(jnp.uint32)) & jnp.uint32(1)) != 0
        bits = bits & valid[:, None]  # invalid lanes contribute nothing
        # block-major flatten: block i = registry key i across C candidates
        return bits.T.reshape(-1)

    def _verify_batch(self, reg_x, reg_y, words32, sig_x, sig_y, h_x, h_y, valid):
        """General launch: masked key segment-sum + batched multi-pairing.

        Shapes: reg_* (L, N) key-group coordinates; words32 (C, 2W) uint32
        packed bitset words (mask unpacked on device, `_unpack_words`);
        sig_* (L, C) and h_* (L, 1 or C) signature-group coordinates;
        valid (C,) bool. Returns (C,) verdicts. The fallback for arbitrary
        signer sets — contiguous-range candidates take `_verify_batch_range`.
        """
        agg = self._dense_aggregate(reg_x, reg_y, words32, valid)
        return self._pairing_tail(agg, sig_x, sig_y, h_x, h_y, valid)

    @jax.named_scope("agg")
    def _dense_aggregate(self, reg_x, reg_y, words32, valid):
        """Per-candidate aggregate key (projective, batch C) of arbitrary
        signer sets: the registry tiled block-major across candidates,
        masked by the bitset words, tree-summed."""
        C = self.batch_size
        kg = self.kg
        # the registry's size as the bank handed in has it, not `self.n`: a
        # plane's lanes run one program, traced by whichever asked first
        n = jax.tree_util.tree_leaves(reg_x)[0].shape[1]
        mask = self._unpack_words(words32, valid, n)
        tile = lambda a: jnp.repeat(a, C, axis=1)  # (L, N) -> (L, N*C)
        P2 = kg.from_affine(_tree(tile, reg_x), _tree(tile, reg_y))
        return kg.masked_sum(P2, mask, n)

    def _gather_prefix(self, prefix, idx):
        """(C,) int32 -> projective key-group batch from the prefix table."""
        kg = self.kg
        x, y, inf = prefix
        take = lambda a: jnp.take(a, idx, axis=1)
        P = kg.from_affine(_tree(take, x), _tree(take, y))
        return kg.select(jnp.take(inf, idx), kg.infinity(idx.shape[0]), P)

    @jax.named_scope("agg")
    def _range_aggregate(
        self, lo, hi, miss_idx, miss_ok, prefix, reg_x, reg_y, miss_k
    ):
        """Per-candidate aggregate key (projective) =
        prefix[hi] - prefix[lo] - sum(missing signers in the hull).

        prefix/reg_x/reg_y are jit ARGUMENTS, not closure reads: with the
        bank traced as an input, the compiled executable is shape-keyed
        only, so an epoch flip to an equal-size registry reuses it — no
        retrace, no recompile inside the quiesce window. (Capturing
        `self._reg_x` here would bake the construction-time bank in as a
        compile-time constant and every flip would silently keep verifying
        against the OLD validator set.)"""
        kg = self.kg
        hull = kg.add(
            self._gather_prefix(prefix, hi),
            kg.neg(self._gather_prefix(prefix, lo)),
        )
        if miss_k:
            take = lambda a: jnp.take(a, miss_idx, axis=1)
            Pm = kg.from_affine(_tree(take, reg_x), _tree(take, reg_y))
            msum = kg.masked_sum(Pm, miss_ok, miss_k)
            hull = kg.add(hull, kg.neg(msum))
        return hull

    def _agg_fp_muls(self, plan) -> int:
        """Base-field multiplications of `plan`'s `agg` stage, from the key
        group's cost of an addition (ops/curve.py `add_fp_muls`): the dense
        class tree-sums n blocks of C lanes; a range class subtracts two
        prefix slots, tree-sums its miss_k-wide patch and subtracts that."""
        kg, C = self.kg, self.batch_size
        if plan.kind == "dense":
            return kg.sum_fp_muls(self.n, C)
        subtraction = C * kg.add_fp_muls
        if not plan.miss_k:
            return subtraction
        return 2 * subtraction + kg.sum_fp_muls(plan.miss_k, C)

    def _verify_batch_range(
        self, lo, hi, miss_idx, miss_ok, sig_x, sig_y, h_x, h_y, valid,
        prefix, reg_x, reg_y, miss_k,
    ):
        """Range-candidate launch: per-candidate aggregate key via the prefix
        table — the O(1)-per-candidate path for Handel traffic, where every
        candidate's signer set is an ID range of the binomial partitioner
        (partitioner.go rangeLevel) minus a few offline members. lo/hi: (C,)
        indices into the prefix table; miss_idx/miss_ok: (miss_k*C,)
        block-major registry indices + validity for the subtraction patch.
        prefix/reg_* are the active bank, passed as arguments (see
        _range_aggregate for why).
        """
        hull = self._range_aggregate(
            lo, hi, miss_idx, miss_ok, prefix, reg_x, reg_y, miss_k
        )
        return self._pairing_tail(hull, sig_x, sig_y, h_x, h_y, valid)

    # -- staged sharded pipeline (mesh_devices > 1) -------------------------

    def _range_agg_kernel(self, miss_k: int):
        """Range aggregation alone as its own executable: point adds only,
        no pairing — compiles in seconds and keeps the mesh out of the
        monolithic jit. The returned callable keeps the per-launch
        (lo, hi, miss_idx, miss_ok) signature and injects the CURRENT
        bank's prefix/registry as trailing jit arguments, so an epoch flip
        reaches already-compiled kernels (and an equal-size flip reuses
        the executable outright)."""
        _ = self._prefix
        fn = self._range_agg_kernels.get(miss_k)
        if fn is None:
            # donate only the per-launch staging inputs; the bank args
            # (4, 5, 6) are device residents and must survive launches
            jitted = self._program(
                f"range_agg{miss_k}",
                partial(self._range_aggregate, miss_k=miss_k),
                donate=(0, 1, 2, 3),
            )

            def fn(lo, hi, miss_idx, miss_ok, _jitted=jitted):
                return _jitted(
                    lo, hi, miss_idx, miss_ok,
                    self._prefix, self._reg_x, self._reg_y,
                )

            self._range_agg_kernels[miss_k] = fn
        return fn

    def _sharded_tail(self, agg, sig_x, sig_y, h_x, h_y, valid):
        """Affine epilogue + candidate-sharded product-of-pairings, staged
        as separate executables with host glue (the structure the dryrun
        validated; see the __init__ comment for why not one jit)."""
        qx, qy, inf = self._affine_kernel(agg)
        ok = np.asarray(valid) & ~np.asarray(inf)
        hxb = jnp.broadcast_to(h_x, sig_x.shape)
        hyb = jnp.broadcast_to(h_y, sig_y.shape)
        neg_y = self._neg_kernel(sig_y)
        shape = qx[0].shape
        bx = (
            jnp.broadcast_to(self._b2x[0], shape),
            jnp.broadcast_to(self._b2x[1], shape),
        )
        by = (
            jnp.broadcast_to(self._b2y[0], shape),
            jnp.broadcast_to(self._b2y[1], shape),
        )
        checks = self._sharded_check(
            ((hxb, hyb), (sig_x, neg_y)), ((qx, qy), (bx, by)), jnp.asarray(ok)
        )
        return np.asarray(checks) & ok

    def _range_kernel(self, miss_k: int):
        # materialize the prefix table HERE, on the host, before jit runs:
        # if the lazy property first fired inside the trace, the cache would
        # permanently hold tracers from a finished trace and every later
        # launch would die with UnexpectedTracerError
        _ = self._prefix
        fn = self._range_kernels.get(miss_k)
        if fn is None:
            # donate every per-launch staging input; h_x/h_y (args 6, 7) are
            # the cached H(m) and the bank args (9, 10, 11) are the
            # device-resident prefix/registry — all must survive launches
            jitted = self._program(
                f"verify_range{miss_k}",
                partial(self._verify_batch_range, miss_k=miss_k),
                donate=(0, 1, 2, 3, 4, 5, 8),
            )

            # same bank-injection wrapper as _range_agg_kernel: callers keep
            # the per-launch signature, epoch flips reach compiled kernels
            def fn(
                lo, hi, miss_idx, miss_ok, sig_x, sig_y, h_x, h_y, valid,
                _jitted=jitted,
            ):
                return _jitted(
                    lo, hi, miss_idx, miss_ok, sig_x, sig_y, h_x, h_y, valid,
                    self._prefix, self._reg_x, self._reg_y,
                )

            self._range_kernels[miss_k] = fn
        return fn

    # -- RLC combined-check launch class (models/rlc.py) --------------------

    # MSM digit width: 64-bit scalars run in 16 windowed steps of 15
    # buckets each (ops/curve.py Curve.msm)
    RLC_WINDOW = 4

    def _rlc_msm_tail(self, agg, sig_x, sig_y, r_bits, group_oh, valid):
        """Shared MSM stage: per-candidate aggregates (projective G2, batch
        C) + signature lanes -> (S, X_g) in affine.

        S = sum_j r_j·sig_j is a G1 MSM over the C signature lanes (C
        blocks of batch 1); X_g = sum_{j in group g} r_j·apk_j tiles each
        candidate across the G group lanes (index j*G + g) with the scalar
        bits gated by the group one-hot, so one G2 MSM computes every
        message group at once. Scalars are masked to the launch hull by
        zeroing invalid lanes' bit columns — those lanes contribute the
        identity. The affine epilogue converts each output batch in one
        stacked-inversion `to_affine` call."""
        C = self.batch_size
        g1, g2 = self.curves.g1, self.curves.g2
        G = group_oh.shape[0]
        rb = r_bits * valid[None, :].astype(r_bits.dtype)
        S = g1.msm(g1.from_affine(sig_x, sig_y), rb, C, window=self.RLC_WINDOW)
        tree = jax.tree_util.tree_map
        tiled = tree(
            lambda a: jnp.broadcast_to(
                a.reshape(a.shape[:-1] + (C, 1)), a.shape[:-1] + (C, G)
            ).reshape(a.shape[:-1] + (C * G,)),
            agg,
        )
        rb2 = (rb[:, :, None] * group_oh.T[None, :, :].astype(rb.dtype)).reshape(
            rb.shape[0], C * G
        )
        X = g2.msm(tiled, rb2, C, window=self.RLC_WINDOW)
        sx, sy, s_inf = g1.to_affine(S)
        xx, xy, x_inf = g2.to_affine(X)
        return sx, sy, s_inf, xx, xy, x_inf

    def _rlc_msm_range(
        self, lo, hi, miss_idx, miss_ok, sig_x, sig_y, r_bits, group_oh,
        valid, prefix, reg_x, reg_y, miss_k,
    ):
        agg = self._range_aggregate(
            lo, hi, miss_idx, miss_ok, prefix, reg_x, reg_y, miss_k
        )
        return self._rlc_msm_tail(agg, sig_x, sig_y, r_bits, group_oh, valid)

    def _rlc_msm_dense(
        self, words32, sig_x, sig_y, r_bits, group_oh, valid, reg_x, reg_y
    ):
        agg = self._dense_aggregate(reg_x, reg_y, words32, valid)
        return self._rlc_msm_tail(agg, sig_x, sig_y, r_bits, group_oh, valid)

    def _rlc_check(self, sx, sy, s_inf, xx, xy, x_inf, h_gx, h_gy, g_occ):
        """(G+1)-lane product-of-pairings with ONE shared final exponentiation:
        lanes 0..G-1 carry e(H(m_g), X_g), lane G carries e(-S, B2). Masked
        lanes contribute 1 — which IS the factor an infinity operand would
        contribute (e(·, O) = e(O, ·) = 1), so infinity and padding lanes
        mask out without changing the product. Returns the (1,) verdict."""
        T, F = self.curves.T, self.curves.F
        b2x = T.f2_pack([self.ref.G2_GEN[0]])
        b2y = T.f2_pack([self.ref.G2_GEN[1]])
        px = jnp.concatenate([h_gx, sx], axis=1)
        py = jnp.concatenate([h_gy, F.neg(sy)], axis=1)
        qx = (
            jnp.concatenate([xx[0], b2x[0]], axis=1),
            jnp.concatenate([xx[1], b2x[1]], axis=1),
        )
        qy = (
            jnp.concatenate([xy[0], b2y[0]], axis=1),
            jnp.concatenate([xy[1], b2y[1]], axis=1),
        )
        lane_mask = jnp.concatenate([g_occ & ~x_inf, ~s_inf])
        return self.pairing.pairing_check((px, py), (qx, qy), lane_mask, 1)

    def _rlc_msm_kernel(self, kind: str, miss_k: int, G: int):
        """MSM/aggregation stage as its own executable per launch class —
        point adds only, no pairing, so it compiles in seconds and can be
        profiled (or host-checked, scripts/rlc_smoke.py) standalone. Same
        bank-injection wrapper as `_range_agg_kernel`: epoch flips reach
        compiled kernels. G rides in the key for the class bookkeeping;
        the executable itself specializes on the group_oh shape."""
        key = (kind, miss_k, G)
        fn = self._rlc_msm_kernels.get(key)
        if fn is None:
            if kind == "range":
                _ = self._prefix
                jitted = jax.jit(
                    _named(
                        partial(self._rlc_msm_range, miss_k=miss_k),
                        f"rlc_msm_range{miss_k}",
                    ),
                    # per-launch staging + scalar operands donate; the bank
                    # args (9, 10, 11) are device residents
                    donate_argnums=tuple(range(9)) if self._donate else (),
                )

                def fn(
                    lo, hi, miss_idx, miss_ok, sig_x, sig_y, r_bits,
                    group_oh, valid, _jitted=jitted,
                ):
                    return _jitted(
                        lo, hi, miss_idx, miss_ok, sig_x, sig_y, r_bits,
                        group_oh, valid,
                        self._prefix, self._reg_x, self._reg_y,
                    )

            else:
                jitted = jax.jit(
                    _named(self._rlc_msm_dense, "rlc_msm_dense"),
                    donate_argnums=tuple(range(6)) if self._donate else (),
                )

                def fn(
                    words32, sig_x, sig_y, r_bits, group_oh, valid,
                    _jitted=jitted,
                ):
                    return _jitted(
                        words32, sig_x, sig_y, r_bits, group_oh, valid,
                        self._reg_x, self._reg_y,
                    )

            self._rlc_msm_kernels[key] = fn
        return fn

    def _rlc_check_kernel(self, G: int):
        fn = self._rlc_check_kernels.get(G)
        if fn is None:
            fn = jax.jit(_named(self._rlc_check, f"rlc_check{G}"))
            self._rlc_check_kernels[G] = fn
        return fn

    def _rlc_combined_launch(self, items, sub):
        """One combined RLC check over candidate indices `sub` of `items`
        ((msg, bitset, sig) triples, pre-screened valid): fresh 64-bit
        scalars, message-grouped G2 MSM (n_groups quantized to the next
        power of two), (G+1)-lane pairing tail. Returns the (1,) device
        verdict and the launch's seq — async like every dispatch; staging
        reuse and fencing follow the ordinary launch contract (`_launch`)."""
        C = self.batch_size
        msgs = [items[j][0] for j in sub]
        uniq: dict[bytes, int] = {}
        gid = [uniq.setdefault(m, len(uniq)) for m in msgs]
        M = len(uniq)
        G = 1
        while G < M:
            G *= 2

        def operands():
            rs = rlc.draw_scalars(len(sub), self._rlc_rng)
            r_bits = np.zeros((rlc.SCALAR_BITS, C), np.uint32)
            r_bits[:, : len(sub)] = np.asarray(self.curves.scalar_bits64(rs))
            group_oh = np.zeros((G, C), bool)
            group_oh[gid, np.arange(len(sub))] = True
            # per-group H(m) columns; padded groups repeat the last real
            # column (masked out by g_occ, any finite h keeps the math
            # well-defined)
            cols = [self._h_cols(m) for m in uniq]  # insertion order = gid
            hx = np.concatenate(
                [c[0] for c in cols] + [cols[-1][0]] * (G - M), axis=1)
            hy = np.concatenate(
                [c[1] for c in cols] + [cols[-1][1]] * (G - M), axis=1)
            return tuple(
                self._dput(a)
                for a in (r_bits, group_oh, hx, hy, np.arange(G) < M)
            )

        def run(plan, staged, r_bits, group_oh, hx, hy, g_occ):
            *bank, sig_x, sig_y, valid = staged
            outs = self._rlc_msm_kernel(plan.kind, plan.miss_k, G)(
                *bank, sig_x, sig_y, r_bits, group_oh, valid
            )
            return self._rlc_check_kernel(G)(*outs, hx, hy, g_occ)

        launched = self._launch(
            [(items[j][1], items[j][2]) for j in sub], operands, run
        )
        self.rlc_stats.miller_lanes += G + 1
        self.rlc_stats.final_exp_lanes += 1
        if M > 1:
            self.multi_msg_launches += 1
        return launched

    def _dispatch_rlc(self, items):
        """RLC-mode dispatch: pre-screen validity host-side (the same
        criterion the packer applies), launch the combined check over the
        valid lanes now (async), and defer verdict resolution — including
        any bisection relaunches — to `fetch`."""
        k = len(items)
        valid_j = [
            j
            for j, (_m, bs, sig) in enumerate(items)
            if bs.cardinality() > 0 and getattr(sig, "point", None) is not None
        ]
        vdev, seq = (
            self._rlc_combined_launch(items, valid_j)
            if len(valid_j) > 1
            else (None, None)
        )
        return ("rlc", items, valid_j, vdev, k, seq)

    def _fetch_rlc(self, handle):
        """Resolve an RLC handle: a passing combined check accepts every
        valid lane; a failing one bisects with fresh scalars down to the
        per-candidate oracle (`_dispatch_one` on the single candidate), so
        culprits are isolated and attributed exactly as per_candidate mode
        would. Invalid lanes are False without any device work."""
        _, items, valid_j, vdev, k, seq = handle
        verdicts = [False] * k
        top = [None if vdev is None else (vdev, seq)]

        def combined(sub):
            launched = top[0]
            top[0] = None
            if launched is None or len(sub) != len(valid_j):
                launched = self._rlc_combined_launch(items, sub)
            return self._pull(*launched, 1)[0]

        def oracle(j):
            msg, bs, sig = items[j]
            return self._pull(*self._dispatch_one(msg, [(bs, sig)]), 1)[0]

        for j, ok in rlc.bisect_verify(
            valid_j, combined, oracle, self.rlc_stats
        ).items():
            verdicts[j] = ok
        return verdicts

    # -- host entry points --------------------------------------------------

    def _h_point(self, msg: bytes):
        cached = self._h_cache.get(msg)
        if cached is None:
            h = self._hash_to_sig_group(msg)
            pack = self.sg.ops.pack
            cached = (self._dput(pack([h[0]])), self._dput(pack([h[1]])))
            self._h_cache[msg] = cached
        return cached

    # dispatch-ahead bound for batch_verify: at most this many chunks'
    # device buffers in flight ahead of the fetch cursor (mirrors the
    # service's max_inflight; an unbounded window kept EVERY chunk's
    # uploads resident on device simultaneously — ADVICE r5 #3)
    MAX_DISPATCH_AHEAD = 4

    def batch_verify(
        self,
        msg: bytes,
        requests: Sequence[tuple[BitSet, BN254Signature]],
    ) -> list[bool]:
        """Verify up to batch_size (global bitset, aggregate sig) candidates
        in one device launch; longer request lists run in several launches.

        Launches are PIPELINED: a chunk is dispatched (enqueued on the
        device — jax dispatch is async) before earlier verdict arrays are
        pulled back to the host, so the per-dispatch round trip (not
        measured on this machine) overlaps chip compute of the launches behind it instead of
        serializing with it — but at most MAX_DISPATCH_AHEAD chunks ahead
        of the fetch cursor, bounding device-resident input buffers. The
        reference's loop verifies one signature at a time on the caller's
        goroutine (processing.go:258-287)."""
        out: list[bool] = []
        window: list = []
        for i in range(0, len(requests), self.batch_size):
            if len(window) >= self.MAX_DISPATCH_AHEAD:
                out.extend(self.fetch(window.pop(0)))
            window.append(self.dispatch(msg, requests[i : i + self.batch_size]))
        for h in window:
            out.extend(self.fetch(h))
        return out

    def dispatch(self, msg, requests):
        """Enqueue one launch (≤ batch_size candidates); returns an opaque
        handle for `fetch`. On the single-device path the device work is in
        flight when this returns (jax async dispatch) and `fetch` blocks on
        the verdicts. On the mesh path the staged pipeline's host glue
        (`_sharded_tail`) completes the launch before returning — there
        `fetch` is effectively a no-op and launch wall time lands on the
        dispatch side of the monitor plane. In RLC mode the handle carries
        the in-flight combined check; bisection (if any) runs at fetch."""
        if self.batch_check == "rlc":
            return self._dispatch_rlc([(msg, bs, sig) for bs, sig in requests])
        verdicts, seq = self._dispatch_one(msg, requests)
        return (verdicts, len(requests), seq)

    def fetch(self, handle) -> list[bool]:
        """Block until a dispatched launch's verdicts arrive; host-ordered."""
        if isinstance(handle[0], str):  # ("rlc", ...)
            return self._fetch_rlc(handle)
        verdicts, k, seq = handle
        return self._pull(verdicts, seq, k)

    @staticmethod
    def launch_seq(handle) -> int | None:
        """The number of the launch a `dispatch*` handle stands for (None:
        an RLC handle that launched nothing yet) — what the service puts
        in its launch spans beside the engine's own stages."""
        return handle[-1]

    def _pull(self, verdicts, seq, k: int) -> list[bool]:
        """The fetch side of ONE launch: wait for its verdicts
        (fetch_wait), copy them to the host and make the first `k` a list
        of booleans (fetch_copy)."""
        clock = self.stage_clock
        with clock.stage("fetch_wait", seq):
            if isinstance(verdicts, jax.Array):
                verdicts.block_until_ready()
        with clock.stage("fetch_copy", seq):
            out = np.asarray(verdicts)[:k].tolist()
        self.host_fetch_launches += 1
        return out

    # -- batched aggregate combine (store.py merge path) --------------------

    def _combine_kernel(self, k: int):
        """One masked signature-group tree-sum + batch affine convert per
        group-width class (k quantized to powers of two so a handful of
        executables cover every merge shape). Point adds only — compiles in
        seconds, nothing pairing-shaped."""
        fn = self._combine_kernels.get(k)
        if fn is None:
            sg = self.sg

            def kern(px, py, pz, mask):
                return sg.to_affine(sg.masked_sum((px, py, pz), mask, k))

            kern.__name__ = f"combine{k}"
            fn = jax.jit(kern)
            self._combine_kernels[k] = fn
        return fn

    def combine_batch(self, groups, compiled_only: bool = False):
        """Sum many groups of signature-group points — aggregate-signature
        merges — in one vmap'd launch per batch_size chunk.

        `groups` is a sequence of point sequences (affine scalar-oracle
        tuples, None = infinity); returns one combined affine point (or
        None) per group. This is the device replacement for the store's
        per-contribution `Signature.combine` host calls: `SignatureStore`
        merge/patch chains and the partitioner's level combination hand
        their whole point set here via `core/processing.py CombineShim` and
        pay one launch instead of one host pairing-library add per point.

        `compiled_only=True` (the CombineShim path) declines — None result
        entries, caller folds on the host — any chunk whose quantized
        group-width class has no compiled kernel yet, so a protocol round
        can NEVER stall on a mid-run combine compile (warmup covers the
        common classes; see `warmup`). Declines are indistinguishable from
        a legitimate infinity sum, which callers must treat the same way:
        redo on the host.
        """
        out = []
        for i in range(0, len(groups), self.batch_size):
            out.extend(
                self._combine_chunk(groups[i : i + self.batch_size],
                                    compiled_only)
            )
        return out

    def _combine_chunk(self, groups, compiled_only: bool = False):
        C = self.batch_size
        kmax = max((len(g) for g in groups), default=1)
        k = 2
        while k < kmax:
            k *= 2
        if compiled_only and k not in self._combine_kernels:
            return [None] * len(groups)
        # block-major grid: block i = element i of every group's sum
        flat = [None] * (k * C)
        mask = np.zeros((k, C), bool)
        for j, g in enumerate(groups):
            for i, p in enumerate(g):
                flat[i * C + j] = p
                mask[i, j] = p is not None
        P = self.sg.pack(flat)
        out = self._combine_kernel(k)(*P, jnp.asarray(mask.reshape(-1)))
        return self.sg.unpack_affine(*out)[: len(groups)]

    def warmup(self, multi_msg: bool = False) -> int:
        """Compile every kernel a verification round can reach, up front.

        `multi_msg=True` additionally compiles the per-lane-h variant of
        the common range class (the `dispatch_multi` shape a multi-tenant
        service reaches once sessions with distinct messages coalesce into
        one launch) — off by default because single-tenant runs never hit
        it and each variant is a full pairing-graph compile.

        Dispatches one synthetic launch per reachable input class — range
        kernel at miss_k=8, at miss_k=64, at the registry's wide width
        (n // 4, `patch_widths`), dense fallback — so no round ever stalls
        on a mid-run XLA compile (before this, the first candidate in a new
        hole-count class blocked its whole round). Classes a registry of
        this size cannot produce are skipped: the 64-hole class needs an
        11-wide hull, the dense fallback a (MISS_CAP+3)-wide one, the wide
        class an n // 4 over MISS_CAP. Returns the number of launches
        issued. Called at scheme construction
        (BN254JaxConstructor.prepare).
        """
        shapes: list[list[int]] = [
            # zero holes -> miss_k=8 class (also builds the prefix table)
            list(range(min(self.n, 2)))
        ]
        if self.n >= 11:
            # hull [0, 11) with 9 holes -> miss_k=64 class
            shapes.append([0, 10])
        if self.patch_widths[-1] > self.MISS_CAP:
            # MISS_CAP+1 holes -> the wide class
            shapes.append([0, self.MISS_CAP + 2])
        if self.n >= self.MISS_CAP + 3:
            # the whole registry as hull, n-2 holes -> dense masked-sum
            # fallback (past the wide class where there is one)
            shapes.append([0, self.n - 1])
        sig = _WarmupSig(self.sg.gen)
        launches = 0
        for signers in shapes:
            bs = BitSet(self.n)
            for i in signers:
                bs.set(i, True)
            # in RLC mode a single-candidate dispatch resolves through the
            # per-candidate oracle at fetch, so this loop compiles the
            # per-candidate kernel classes (the bisection floor) either way
            self.fetch(self.dispatch(b"bn254-device-warmup", [(bs, sig)]))
            launches += 1
        if self.batch_check == "rlc":
            # compile the RLC combined-check classes (MSM stage + (G+1)-lane
            # pairing tail) with a two-candidate launch per plan class. The
            # warmup sig is not a valid signature, so each combined check
            # FAILS and the bisection path — fresh-scalar singleton oracles
            # — runs too: exactly the kernels a forged batch needs hot.
            for signers in shapes:
                bs = BitSet(self.n)
                for i in signers:
                    bs.set(i, True)
                self.fetch(
                    self.dispatch(b"bn254-device-warmup", [(bs, sig)] * 2)
                )
                launches += 1
        if multi_msg and self.n >= 2:
            bs1, bs2 = BitSet(self.n), BitSet(self.n)
            bs1.set(0, True)
            bs2.set(1, True)
            self.fetch(
                self.dispatch_multi(
                    [
                        (b"bn254-device-warmup-m1", None, bs1, sig),
                        (b"bn254-device-warmup-m2", None, bs2, sig),
                    ]
                )
            )
            launches += 1
        # combine classes k=2/4/8 cover pairwise merges through wide patch
        # chains (point adds only — seconds each, not a pairing graph);
        # the CombineShim path only uses classes compiled HERE
        # (combine_batch(compiled_only=True)), so wider merges host-fold
        # instead of ever compiling mid-round
        for k in (2, 4, 8):
            self.combine_batch([[self.sg.gen] * k])
            launches += 1
        # warmup launches must not skew the host-cost telemetry
        self.reset_host_counters()
        return launches

    def reset_host_counters(self) -> None:
        """Zero the host-stage cost counters (warmup and phase
        boundaries: accumulation must start at the phase, not at
        construction). Launch numbering goes on: a seq is an identity."""
        self.stage_clock.reset()
        self.host_pack_launches = 0
        self.host_dispatch_launches = 0
        self.host_fetch_launches = 0
        self.class_launches = dict.fromkeys(LAUNCH_CLASSES, 0)
        self.patch_slots = 0
        self.patch_holes = 0
        self.miller_steps = 0
        self.miller_add_steps = 0
        self.miller_acc_fp_muls = 0
        self.agg_fp_muls = 0
        self.rlc_stats = rlc.RlcStats()

    # widest NARROW missing-signer patch: a launch whose largest hole count
    # is over this takes the registry's wide class where it has one
    # (`patch_widths`), else the dense masked-sum kernel
    MISS_CAP = 64

    @property
    def patch_widths(self) -> tuple[int, ...]:
        """The range classes' patch widths, ascending: 8, MISS_CAP and,
        where it exceeds MISS_CAP, n // 4 — the most holes a
        top-level range (n / 2 ids) can have while patching them is still
        cheaper than summing its signers, so one wide class holds every
        level aggregate of a committee with up to half of a range absent.
        Each width is one compile of the pairing graph; the wide one is
        read off the registry, never configured."""
        wide = self.n // 4
        return (8, self.MISS_CAP, wide) if wide > self.MISS_CAP else (
            8, self.MISS_CAP)

    def _patch_width(self, max_holes: int) -> int:
        """The class of a launch, from its largest hole count: the
        narrowest patch width that holds it, 0 = dense."""
        return next((k for k in self.patch_widths if max_holes <= k), 0)

    def _count_class(self, plan) -> None:
        """One launch of `plan`'s class (see `class_launches`), the
        Miller-loop steps its program runs with the base-field
        multiplications a pair of their accumulator updates, as the pairing
        that built the program counts them, and the base-field
        multiplications of its `agg` stage, as the key group counts them."""
        self.agg_fp_muls += self._agg_fp_muls(plan)
        self.miller_steps += self.pairing.miller_steps
        self.miller_add_steps += self.pairing.miller_add_steps
        self.miller_acc_fp_muls += self.pairing.miller_acc_fp_muls
        if plan.kind == "dense":
            name = "dense"
        elif plan.miss_k > self.MISS_CAP:
            name = "range_wide"
            self.patch_slots += plan.miss_k * int(np.count_nonzero(plan.valid))
            self.patch_holes += int(np.count_nonzero(plan.miss_ok))
        else:
            name = f"range{plan.miss_k}"
        self.class_launches[name] += 1

    @staticmethod
    def _pack_sig_limbs(ops, pts, out):
        """Pack signature coordinate limbs (`ops`: the signature group's
        element algebra) into staging, uniquing by point object identity
        first: Handel traffic re-delivers the same aggregate (one
        signature OBJECT fanned across lanes after dedup coalescing), so the
        bigint limb conversion — the single most expensive per-lane pack op
        — runs once per distinct point, then scatters by fancy index."""
        uniq: dict[int, int] = {}
        inv = np.empty((len(pts),), np.int64)
        upts: list = []
        for j, p in enumerate(pts):
            i = uniq.get(id(p))
            if i is None:
                i = uniq[id(p)] = len(upts)
                upts.append(p)
            inv[j] = i
        ux = ops.pack_np([p[0] for p in upts])
        uy = ops.pack_np([p[1] for p in upts])
        for dst, src in zip(
            _cols(out.sig_x) + _cols(out.sig_y), _cols(ux) + _cols(uy)
        ):
            dst[:] = src[:, inv]

    # all-ones uint64, for the hull word-mask construction below
    _U64_ONES = np.uint64(0xFFFFFFFFFFFFFFFF)

    @classmethod
    def _ones_below(cls, c):
        """(1 << c) - 1 for per-element widths c in [0, 64] (uint64-safe:
        numpy's shift by 64 is undefined, so full words take a where)."""
        shift = np.minimum(c, np.uint64(63))
        return np.where(
            c >= 64, cls._U64_ONES, (np.uint64(1) << shift) - np.uint64(1)
        )

    @staticmethod
    def _hull(words):
        """Per row of bitset words (k, W), uint64: its cardinality and its
        hull [lo, hi), the first set bit and one past the last (0, 0 for an
        empty row), as int64 — so `hi - lo - card` is the row's hole count.
        No unpacking: the first and last nonzero word of a row, then a
        trailing-zero / leading-bit scan of just those edge words. The one
        place the packer (`_pack_into`) and `launch_class` read holes from."""
        card = np.bitwise_count(words).sum(axis=1, dtype=np.int64)
        wnz = words != 0
        nonempty = wnz.any(axis=1)
        W = words.shape[1]
        rows = np.arange(words.shape[0])
        fw = wnz.argmax(axis=1)
        lw = (W - 1) - wnz[:, ::-1].argmax(axis=1)
        wf = words[rows, fw]
        tz = np.bitwise_count(  # trailing zeros: popcount((w & -w) - 1)
            (wf & (~wf + np.uint64(1))) - np.uint64(1)
        ).astype(np.int64)
        v = words[rows, lw].copy()  # leading bit: smear right, popcount - 1
        for s in (1, 2, 4, 8, 16, 32):
            v |= v >> np.uint64(s)
        msb = np.bitwise_count(v).astype(np.int64) - 1
        lo = np.where(nonempty, fw * 64 + tz, 0)
        hi = np.where(nonempty, lw * 64 + msb + 1, 0)  # one past last bit
        return card, lo, hi

    def launch_class(self, bitsets) -> list[int]:
        """The launch class each candidate needs, from its bitset alone:
        the narrowest patch width of `patch_widths` that holds its hull
        holes, 0 = dense — what `_pack_into` gives a launch that holds only
        that candidate. The service plans a launch for one class with it
        (parallel/batch_verifier.py); the packer's largest-hole rule still
        decides what a launch runs, so a candidate classed narrower than
        its launch stays correct."""
        if not len(bitsets):
            return []
        card, lo, hi = self._hull(np.stack([bs.words() for bs in bitsets]))
        widths = np.asarray(self.patch_widths)
        at = np.searchsorted(widths, hi - lo - card)  # first width >= holes
        return np.append(widths, 0)[at].tolist()

    def _pack_requests(self, requests, seq: int | None = None) -> "LaunchPlan":
        """Vectorized launch packing: requests -> device-input arrays, as
        two timed stages of launch `seq`: the wait on the staging set's
        fence (fence_wait — under saturation about one device launch), then
        the packing itself (pack).

        Bitsets hand over their packed uint64 words (BitSet.words, zero
        copy) straight into the rotated staging set — the same words array
        is later the device-transfer source (zero-copy: no dense bit matrix
        is materialized on the host at all). Cardinalities come from one
        `np.bitwise_count` over the words, range bounds from word-level
        argmax scans plus branch-free bit scans of the two edge words, and
        the missing-signer patch unpacks only the hull-masked COMPLEMENT
        words (skipped entirely for hole-free batches, the common Handel
        case). Staging buffers ROTATE across `stage_sets` sets: a returned
        plan's views stay valid until the rotation wraps back onto its set
        (see _StagingSet for the fence that enforces this against
        still-in-flight launches).

        Bit-identical to the per-candidate construction that
        tests/test_dispatch_pack.py keeps as the readable oracle
        (property-tested across rotation boundaries).
        """
        self._stage_idx = (self._stage_idx + 1) % len(self._stage)
        st = self._stage[self._stage_idx]
        clock = self.stage_clock
        with clock.stage("fence_wait", seq):
            if st.fence is not None:
                # the last launch that read this set must have consumed its
                # inputs before we overwrite them (no-op once it completed)
                st.fence.block_until_ready()
                st.fence = None
        with clock.stage("pack", seq, cpu=True):
            return self._pack_into(st, requests)

    def _pack_into(self, st: _StagingSet, requests) -> "LaunchPlan":
        """The packing itself, into staging set `st` (see `_pack_requests`)."""
        C = self.batch_size
        n = self.n
        k = len(requests)
        words = st.words
        words[:] = 0
        valid = st.valid
        valid[:] = False
        sig_pts: list = []
        for j, (bs, sig) in enumerate(requests):
            if len(bs) != n:
                raise ValueError("bitset length != registry size")
            words[j, :] = bs.words()
            sig_pts.append(getattr(sig, "point", None))

        card, hull_lo, hull_hi = self._hull(words)
        if k:
            valid[:k] = (card[:k] > 0) & np.fromiter(
                (p is not None for p in sig_pts), bool, count=k
            )
        words[~valid] = 0  # invalid lanes contribute nothing
        W = words.shape[1]
        lo, hi = st.lo, st.hi
        lo[:] = np.where(valid, hull_lo, 0)
        hi[:] = np.where(valid, hull_hi, 0)
        max_holes = int(np.where(valid, hull_hi - hull_lo - card, 0).max())

        # lanes with a point but an empty bitset stay masked placeholders,
        # like the old loop (valid gating covers both cases)
        gen = self.sg.gen
        pts = [pt if valid[j] else gen for j, pt in enumerate(sig_pts)]
        pts += [gen] * (C - k)  # pad lanes
        self._pack_sig_limbs(self.sg.ops, pts, st)

        # quantize the patch width to the few classes of `patch_widths`, so
        # that no more range kernels ever compile (each variant jit-compiles
        # the whole pairing graph; a fresh hole-count class mid-run would
        # otherwise stall that verification round on XLA)
        miss_k = self._patch_width(max_holes)
        if not miss_k:
            # dense fallback: the words themselves are the device input
            # (mask unpacked on device by _unpack_words)
            return LaunchPlan(
                "dense", 0, None, None, None, None, words, None,
                st.sig_x, st.sig_y, valid,
            )

        miss_idx = st.miss[:miss_k]
        miss_ok = st.miss_ok[:miss_k]
        miss_idx[:] = 0
        miss_ok[:] = False
        if max_holes > 0:
            # unpack only the hull-masked complement: hole bits = ~words
            # inside each lane's [lo, hi) hull, built as a (C, W) word mask
            base = np.arange(W, dtype=np.int64) * 64
            lo_c = np.clip(lo.astype(np.int64)[:, None] - base, 0, 64)
            hi_c = np.clip(hi.astype(np.int64)[:, None] - base, 0, 64)
            hull = self._ones_below(hi_c.astype(np.uint64)) ^ self._ones_below(
                lo_c.astype(np.uint64)
            )
            missw = hull & ~words
            mbits = np.unpackbits(
                missw.view(np.uint8), axis=1, count=n, bitorder="little"
            ).view(np.bool_)
            rj, cj = np.nonzero(mbits)  # row-major: per-candidate, ascending
            if rj.size:
                counts = mbits.sum(axis=1)
                offs = np.concatenate(([0], np.cumsum(counts)[:-1]))
                pos = np.arange(rj.size) - offs[rj]
                miss_idx[pos, rj] = cj
                miss_ok[pos, rj] = True
        return LaunchPlan(
            "range", miss_k, lo, hi, miss_idx, miss_ok, words, None,
            st.sig_x, st.sig_y, valid,
        )

    def _stage_plan(self, plan):
        """Explicit host→device handoff of one plan's staging views.

        One `jax.device_put` per array, no snapshot copies: the rotation +
        fence contract of `_pack_requests` guarantees a still-in-flight
        launch's (possibly aliased, on the CPU client) buffers are never
        overwritten. Explicit puts are the ONLY host→device transfers a
        steady-state launch performs — everything else (registry, prefix
        table, cached H(m)) is device-resident — which is what lets the
        transfer-guard test allowlist staging while banning implicit
        transfers outright. Returns the per-kind device-argument tuple
        (committed to this engine's pinned chip when one was given).
        """
        dp = self._dput
        if plan.kind == "range":
            return (
                dp(plan.lo),
                dp(plan.hi),
                dp(plan.miss_idx.reshape(-1)),
                dp(plan.miss_ok.reshape(-1)),
                dp(plan.sig_x),
                dp(plan.sig_y),
                dp(plan.valid),
            )
        return (
            dp(plan.words.view(np.uint32)),
            dp(plan.sig_x),
            dp(plan.sig_y),
            dp(plan.valid),
        )

    def _run_plan(self, plan, staged, h_x, h_y):
        """Launch one staged plan against the kernels. h_x/h_y may be the
        cached per-message (L, 1) arrays (broadcast across lanes) or the
        multi-message (L, C) per-lane columns — the kernels broadcast to
        the signature shape either way, so both shapes share the math; XLA
        compiles one extra variant per kernel class for the wide shape."""
        # Handel candidates are partitioner ID ranges with few holes: the
        # prefix-table fast path; the dense kernel is the arbitrary-set
        # fallback (plan.kind decides, same classes as always)
        # per-candidate pairing-work accounting (the 2C / C baseline the
        # RLC smoke compares against): every plan run pays a 2C-lane
        # Miller batch and a C-lane final exponentiation
        self.rlc_stats.miller_lanes += 2 * self.batch_size
        self.rlc_stats.final_exp_lanes += self.batch_size
        if self.mesh is not None:
            # whole-mesh (latency-plane) launch accounting: the mesh lane's
            # telemetry row (parallel/telemetry.py) reads these
            self.mesh_launches += 1
            self.mesh_candidates += int(np.count_nonzero(plan.valid))
        if plan.kind == "range":
            lo, hi, miss_idx, miss_ok, sig_x, sig_y, valid = staged
            if self.mesh is not None:
                agg = self._range_agg_kernel(plan.miss_k)(
                    lo, hi, miss_idx, miss_ok
                )
                return self._sharded_tail(agg, sig_x, sig_y, h_x, h_y, valid)
            return self._range_kernel(plan.miss_k)(
                lo, hi, miss_idx, miss_ok, sig_x, sig_y, h_x, h_y, valid
            )
        words32, sig_x, sig_y, valid = staged
        if self.mesh is not None:
            # the staged sharded pipeline still wants the dense (n, C)
            # mask; unpack it host-side here — the mesh path's host glue
            # already materializes per-stage arrays, so this is not the
            # single-chip hot path
            mask = (
                np.unpackbits(
                    plan.words.view(np.uint8),
                    axis=1,
                    count=self.n,
                    bitorder="little",
                )
                .view(np.bool_)
                .T.copy()
            )
            # pre-pad to the device multiple (padded rows False — the rule
            # sharded_masked_sum_g2 applies internally) and place by
            # partition rule, one registry-axis shard per chip, so the
            # shard_map region never re-shards a replicated mask
            if self._mesh_pad:
                mask = np.pad(mask, ((0, self._mesh_pad), (0, 0)))
            mask = self._mesh_put["mask"](mask)
            # registry operands are the PRE-PADDED mesh-resident shards
            # committed at construction (one per chip); only the per-launch
            # mask crosses the host boundary here
            (rx0, rx1), (ry0, ry1) = self._reg_sharded
            agg = self._sharded_sum(rx0, rx1, ry0, ry1, mask)
            return self._sharded_tail(agg, sig_x, sig_y, h_x, h_y, valid)
        return self._kernel(
            self._reg_x,
            self._reg_y,
            words32,
            sig_x,
            sig_y,
            h_x,
            h_y,
            valid,
        )

    def _launch(self, requests, operands, run):
        """The host side of ONE launch, the body every dispatch path shares:
        number it, pack it (`_pack_requests`: fence_wait, pack), hand the
        plan's arrays and `operands()` — the H(m) columns, or the RLC
        scalars and group operands — to the device (stage), then call the
        kernels through `run(plan, staged, *operands)` until the jitted call
        returns (enqueue). Returns (verdicts, seq); on the single-device
        path the device work is in flight."""
        seq = self._next_seq
        self._next_seq += 1
        clock = self.stage_clock
        plan = self._pack_requests(requests, seq)
        self.host_pack_launches += 1
        self._count_class(plan)
        with clock.stage("stage", seq):
            staged = self._stage_plan(plan)
            ops = operands()
        with clock.stage("enqueue", seq):
            verdicts = run(plan, staged, *ops)
            if isinstance(verdicts, jax.Array):
                # fence the staging set this launch reads: _pack_requests
                # blocks on it before the rotation wraps back onto these
                # buffers
                self._stage[self._stage_idx].fence = verdicts
        self.host_dispatch_launches += 1
        return verdicts, seq

    def _dispatch_one(self, msg, requests):
        return self._launch(
            requests, lambda: self._h_point(msg), self._run_plan
        )

    # -- multi-message launches (multi-tenant service coalescing) -----------

    def _h_cols(self, msg: bytes):
        """Host-side (L, 1) limb columns of H(msg) — the np counterpart of
        `_h_point`'s device-resident cache, kept separately so building a
        per-lane h matrix never pulls a device array back to the host."""
        cached = self._h_np_cache.get(msg)
        if cached is None:
            h = self._hash_to_sig_group(msg)
            pack_np = self.sg.ops.pack_np
            cached = (pack_np([h[0]]), pack_np([h[1]]))
            self._h_np_cache[msg] = cached
        return cached

    def _h_lanes(self, msgs):
        """(L, C) per-lane H(m) arrays for a mixed-message launch, built by
        scattering the per-distinct-message columns (hash-to-curve runs
        once per distinct message, cached) and explicitly device_put —
        the same staging discipline as `_stage_plan`."""
        C = self.batch_size
        uniq: dict[bytes, int] = {}
        inv = np.empty((len(msgs),), np.int64)
        cols: list[tuple] = []
        for j, m in enumerate(msgs):
            i = uniq.get(m)
            if i is None:
                i = uniq[m] = len(cols)
                cols.append(self._h_cols(m))
            inv[j] = i
        # padded lanes are masked invalid; any finite h keeps the math
        # well-defined, so they repeat the last real column
        inv = np.concatenate([inv, np.full((C - len(msgs),), inv[-1])])
        lanes = lambda *col: np.concatenate(col, axis=1)[:, inv]
        hx = _tree(lanes, *(c[0] for c in cols))
        hy = _tree(lanes, *(c[1] for c in cols))
        return self._dput(hx), self._dput(hy)

    def dispatch_multi(self, items):
        """Enqueue one launch whose lanes may carry DIFFERENT messages —
        the multi-tenant service's cross-session coalescing contract
        (parallel/batch_verifier.py): items are (msg, pubkeys, bitset,
        sig); pubkeys are ignored because this device's resident registry
        is the key universe for every lane. A uniform-message batch
        delegates to the ordinary `dispatch` (cached (L, 1) h, no extra
        kernel variant); mixed messages stage per-lane (L, C) h columns
        into the same kernels. Returns a `fetch`-compatible handle.

        In RLC mode mixed messages GROUP rather than widen: the combined
        check groups lanes by message for M+1 Miller loops total, so a
        multi-tenant coalesced launch costs one Miller loop per distinct
        message plus one — not two per candidate."""
        if self.batch_check == "rlc":
            return self._dispatch_rlc([(it[0], it[2], it[3]) for it in items])
        msgs = [it[0] for it in items]
        reqs = [(it[2], it[3]) for it in items]
        if len(set(msgs)) <= 1:
            return self.dispatch(msgs[0] if msgs else b"", reqs)
        verdicts, seq = self._launch(
            reqs, lambda: self._h_lanes(msgs), self._run_plan
        )
        self.multi_msg_launches += 1
        return (verdicts, len(reqs), seq)


class BN254JaxConstructor(BN254Constructor):
    """Constructor whose `batch_verify` runs on the JAX/TPU path.

    The device registry is built lazily from the pubkey sequence of the first
    call (Handel passes the same registry list every time) or eagerly via
    `prepare()`. Marshal/unmarshal and single-sig verify stay host-side.

    Failover (`host_fallback=True`): device/XLA errors — including a compile
    or upload failure inside the lazy prepare — feed a circuit breaker, and
    the batch resolves through the INHERITED host-side serial batch_verify
    (Constructor.batch_verify over the host pubkey objects, i.e. the
    ops/bn254_ref reference math; curve-agnostic, so the BLS12-381 subclass
    inherits the failover too) instead of raising. This covers
    the per-node default-verifier path the same way BatchVerifierService
    covers the shared launch queue (parallel/batch_verifier.py): a dead
    accelerator degrades throughput, it does not stall the node. Request
    errors (ValueError: malformed bitsets) are the caller's bug and
    propagate untouched.
    """

    Device = BN254Device

    def __init__(
        self,
        batch_size: int = 16,
        curves: BN254Curves | None = None,
        mesh_devices: int = 1,
        warmup: bool = True,
        host_fallback: bool = True,
        breaker: CircuitBreaker | None = None,
        fp_backend: str | None = None,
        rns_resident: bool | None = None,
        batch_check: str = "per_candidate",
        rlc_rng: random.Random | None = None,
    ):
        self.batch_size = batch_size
        self.mesh_devices = mesh_devices
        self.fp_backend = fp_backend
        self.rns_resident = rns_resident
        self.batch_check = rlc.validate_batch_check(batch_check)
        self._rlc_rng = rlc_rng
        # fp_backend picks the Field modmul kernel (ops/fp.py backend seam:
        # "cios"/"rns"); an explicit `curves` wins, carrying its own Field
        self.curves = curves or self.Device.Curves(backend=fp_backend)
        self.warmup = warmup
        self.host_fallback = host_fallback
        self.breaker = breaker or CircuitBreaker()
        self.failover_batches = 0
        self.failover_candidates = 0
        self.log = DEFAULT_LOGGER
        self._device: BN254Device | None = None
        self._device_for: int | None = None

    def new_device(self, pubkeys, **placement) -> BN254Device:
        """An engine of this scheme over `pubkeys` with the constructor's
        options; `placement` is where it runs (`jax_device`, `plane_of`:
        parallel/plane.py pins one to each chip)."""
        return self.Device(
            pubkeys,
            batch_size=self.batch_size,
            curves=self.curves,
            mesh_devices=self.mesh_devices,
            rns_resident=self.rns_resident,
            batch_check=self.batch_check,
            rlc_rng=self._rlc_rng,
            **placement,
        )

    def prepare(self, pubkeys: Sequence[BN254PublicKey]) -> BN254Device:
        self._device = self.new_device(pubkeys)
        if self.warmup:
            # compile all reachable kernels NOW, at scheme construction, so
            # no verification round stalls on a mid-run XLA compile
            self._device.warmup()
        # hold the list itself: the id() cache key below is only valid while
        # the original object is alive (id reuse after GC would alias a new
        # registry to the cached one)
        self._reg_list = pubkeys
        self._device_for = id(pubkeys)
        self._reg_keys = [pk.point for pk in pubkeys]
        return self._device

    def _device_of(self, pubkeys) -> BN254Device:
        if self._device is None or self._device.n != len(pubkeys):
            self.prepare(pubkeys)
        elif self._device_for != id(pubkeys):
            # same length, different list object: full content check once per
            # new list identity (a same-size registry rebuilt after churn must
            # NOT verify against stale keys), then adopt the id so repeat
            # calls stay O(1)
            if [pk.point for pk in pubkeys] == self._reg_keys:
                self._reg_list = pubkeys
                self._device_for = id(pubkeys)
            else:
                self.prepare(pubkeys)
        return self._device

    def device_combine(self, groups):
        """Batched aggregate combine for `core/processing.py CombineShim`:
        sum each group of signature points in one device launch. Returns
        None (caller falls back to host serial combine) until the device
        exists — the shim must never force an eager registry upload — or
        when the breaker has the device offline."""
        if self._device is None or not self.breaker.allow():
            return None
        try:
            # compiled_only: a merge shape warmup did not cover host-folds
            # (None entry) rather than stalling the round on an XLA compile
            out = self._device.combine_batch(groups, compiled_only=True)
            self.breaker.record_success()
            return out
        except Exception as e:  # device/XLA failure: host fold instead
            self.breaker.record_failure()
            self.log.warn("bn254_device_combine_error", e)
            return None

    def batch_verify(self, msg, pubkeys, requests) -> list[bool]:
        if not self.host_fallback:
            return self._device_of(pubkeys).batch_verify(msg, requests)
        if self.breaker.allow():
            try:
                out = self._device_of(pubkeys).batch_verify(msg, requests)
                self.breaker.record_success()
                return out
            except ValueError:
                raise  # malformed request, not a device failure
            except Exception as e:
                self.breaker.record_failure()
                self.log.warn("bn254_device_error", e)
        self.failover_batches += 1
        self.failover_candidates += len(requests)
        return super().batch_verify(msg, pubkeys, requests)


class BN254JaxScheme(BN254Scheme):
    """Keygen facade for harness/simulation use: the host scheme's keygen and
    wire formats (incl. unmarshal_public/unmarshal_secret for the registry
    CSV) with the device-verification constructor swapped in."""

    Constructor = BN254JaxConstructor

    def __init__(
        self,
        batch_size: int = 16,
        mesh_devices: int = 1,
        warmup: bool = True,
        fp_backend: str | None = None,
        rns_resident: bool | None = None,
        batch_check: str = "per_candidate",
    ):
        self.constructor = self.Constructor(
            batch_size=batch_size,
            mesh_devices=mesh_devices,
            warmup=warmup,
            fp_backend=fp_backend,
            rns_resident=rns_resident,
            batch_check=batch_check,
        )
