"""RLC batch-verification smoke: verdict parity, counters.

The fast-tier guard for the random-linear-combination batch check
(models/rlc.py + the HostDevice/BN254Device wiring): RLC verdicts must
equal per-candidate verdicts on valid AND forged batches, for both launch
shapes the service dispatches — single-message `dispatch` launches and
mixed-message `dispatch_multi` launches — with the per-launch pairing cost
asserted at M+1 Miller loops / 1 final exponentiation via the RlcStats
kernel counters (against the 2C / C per-candidate baseline). A forged
batch must come back with exactly the per-candidate culprit set, found by
bisection.

Scope note: one CPU core takes minutes of XLA per MSM/pairing-tail graph,
so this smoke drives the host-math RLC engine (native bn254 group ops) —
the combined-check equation, grouping, bisection and counters are the same
code the device path shares via models/rlc.py. The device MSM kernel and
the fused pairing tail compile in the slow tier (tests/test_msm.py,
BN254Device.warmup in rlc mode); here the device side is covered to the
dispatch seam: rlc-mode `BN254Device.dispatch`/`dispatch_multi` route both
packing classes (range + dense) into the rlc handle without a kernel.
Set HANDEL_TPU_RLC_SMOKE_DEVICE=1 to also compile the tiny-shape device
MSM stage and check S/X against the host oracle (minutes of XLA, off by
default in CI).
"""

import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("HANDEL_TPU_PLATFORM", "cpu")

from handel_tpu.core.bitset import BitSet  # noqa: E402
from handel_tpu.models import rlc  # noqa: E402
from handel_tpu.models.bn254 import BN254Scheme  # noqa: E402
from handel_tpu.service.driver import HostDevice  # noqa: E402

N = 16  # registry size
C = 64  # candidates per launch
M = 4  # distinct messages in the mixed-message launch


def build_batch(scheme, keys, pubs, rng, messages, forged=()):
    """C candidates over `messages` distinct messages; indices in `forged`
    carry a wrong-message aggregate signature."""
    from handel_tpu.sim.adversary import forged_signature

    items = []
    for j in range(C):
        msg = messages[j % len(messages)]
        bs = BitSet(N)
        sig = None
        for i in rng.sample(range(N), rng.randrange(2, 6)):
            bs.set(i)
            s = (
                forged_signature(keys[i][0], msg)
                if j in forged
                else keys[i][0].sign(msg)
            )
            sig = s if sig is None else sig.combine(s)
        items.append((msg, pubs, bs, sig))
    return items


def check_parity(scheme, items, label):
    """RLC verdicts == per-candidate verdicts; returns both stat blocks."""
    pc = HostDevice(scheme.constructor)
    v_pc = pc.fetch(pc.dispatch_multi(items))
    dev = HostDevice(
        scheme.constructor, batch_check="rlc", rlc_rng=random.Random(1717)
    )
    v_rlc = dev.fetch(dev.dispatch_multi(items))
    assert v_rlc == v_pc, f"{label}: verdict mismatch {v_rlc} != {v_pc}"
    return v_rlc, dev.rlc_stats, pc.rlc_stats


def main() -> int:
    t0 = time.perf_counter()
    rng = random.Random(0x51C)
    scheme = BN254Scheme()
    keys = [scheme.keygen(i) for i in range(N)]
    pubs = [pk for _, pk in keys]
    single = [b"rlc-smoke-single"]
    multi = [f"rlc-smoke-{m}".encode() for m in range(M)]

    # -- valid batches: one combined check, M+1 Miller loops, 1 final exp --
    for msgs, label in ((single, "single-message"), (multi, "mixed-message")):
        items = build_batch(scheme, keys, pubs, rng, msgs)
        v, st, pst = check_parity(scheme, items, label)
        assert all(v), f"{label}: valid batch rejected"
        m = len(msgs)
        assert st.rlc_launches == 1 and st.bisection_ct == 0, st
        assert st.miller_lanes == m + 1, (
            f"{label}: {st.miller_lanes} Miller lanes, want M+1 = {m + 1}"
        )
        assert st.final_exp_lanes == 1, st
        # the per-candidate baseline the RLC launch replaces: 2C / C
        assert pst.miller_lanes == 2 * C and pst.final_exp_lanes == C, pst
        print(
            f"rlc_smoke: {label} valid batch of {C}: verdict parity, "
            f"{st.miller_lanes} Miller loops + {st.final_exp_lanes} final "
            f"exp (per-candidate: {pst.miller_lanes} + {pst.final_exp_lanes})"
        )

    # -- forged batches: bisection isolates the exact culprit set ----------
    for msgs, label in ((single, "single-message"), (multi, "mixed-message")):
        culprits = set(rng.sample(range(C), 3))
        items = build_batch(scheme, keys, pubs, rng, msgs, forged=culprits)
        v, st, _ = check_parity(scheme, items, label)
        found = {j for j, ok in enumerate(v) if not ok}
        assert found == culprits, f"{label}: isolated {found} != {culprits}"
        assert st.rlc_launches == 1 and st.bisection_ct > 0, st
        assert st.bisection_depth_max >= 1, st
        print(
            f"rlc_smoke: {label} forged batch: bisection isolated "
            f"{sorted(culprits)} in {st.bisection_ct} rechecks "
            f"(depth {st.bisection_depth_max})"
        )

    # -- BLS12-381 inherits via the generic ops seam (tiny: pure-ref math) -
    from handel_tpu.models.bls12_381 import BLS12381Scheme

    bscheme = BLS12381Scheme()
    bkeys = [bscheme.keygen(i) for i in range(4)]
    bops = rlc.host_ops_for(bscheme.constructor)
    bcands = []
    for j, msg in enumerate((b"bls-a", b"bls-b")):
        sk, pk = bkeys[j]
        bcands.append((msg, pk.point, sk.sign(msg).point))
    bst = rlc.RlcStats()
    assert rlc.host_rlc_check(bops, bcands, stats=bst)
    assert bst.miller_lanes == 3 and bst.final_exp_lanes == 1
    bad = [bcands[0], (b"bls-b", bkeys[1][1].point, bkeys[1][0].sign(b"x").point)]
    assert not rlc.host_rlc_check(bops, bad)
    print("rlc_smoke: bls12-381 host ops seam: valid accepted, forged rejected")

    # -- device dispatch seam: both packing classes route into rlc ---------
    import numpy as np  # noqa: F401

    from handel_tpu import native as nat
    from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature
    from handel_tpu.models.bn254_jax import BN254Device
    from handel_tpu.ops import bn254_ref as bn

    n_dev = 130  # > MISS_CAP so the dense class is reachable
    sks = [rng.randrange(1, 1 << 20) for _ in range(n_dev)]
    dpks = [
        BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * n_dev, sks)
    ]
    device = BN254Device(dpks, batch_size=4, batch_check="rlc")
    range_bs = BitSet(n_dev)
    for i in range(8):
        range_bs.set(i)
    dense_bs = BitSet(n_dev)
    dense_bs.set(0)
    dense_bs.set(n_dev - 1)  # full hull, > 64 holes -> dense class
    for i in rng.sample(range(n_dev), 40):
        dense_bs.set(i)
    for bs, kind in ((range_bs, "range"), (dense_bs, "dense")):
        plan = device._pack_requests([(bs, BN254Signature(bn.G1_GEN))])
        assert plan.kind == kind, (kind, plan.kind)
        handle = device.dispatch(b"m", [(bs, BN254Signature(bn.G1_GEN))])
        assert handle[0] == "rlc", handle[0]
    print("rlc_smoke: rlc-mode device routes range + dense packing classes")

    if os.environ.get("HANDEL_TPU_RLC_SMOKE_DEVICE") == "1":
        _device_msm_phase(device, dpks, rng)

    print(f"rlc_smoke: ok (total {time.perf_counter() - t0:.1f}s)")
    return 0


def _device_msm_phase(device, dpks, rng):
    """Optional (HANDEL_TPU_RLC_SMOKE_DEVICE=1): compile the tiny-shape
    device MSM stage for the range class and check S / X against the host
    scalar oracle. Minutes of XLA on one CPU core."""
    import numpy as np

    from handel_tpu import native as nat
    from handel_tpu.models.bn254 import BN254Signature
    from handel_tpu.ops import bn254_ref as bn

    items = []
    for j in range(device.batch_size):
        bs = BitSet(len(dpks))
        lo = rng.randrange(0, 8)
        for i in range(lo, lo + 4):
            bs.set(i)
        items.append((f"dev-{j % 2}".encode(), bs,
                      BN254Signature(bn.g1_mul(bn.G1_GEN, j + 2))))
    handle = device._dispatch_rlc(items)
    verdicts = device._fetch_rlc(handle)
    # forged inputs (generator-multiple sigs): every candidate must fail,
    # via a combined check that *ran on device* and bisected to the oracle
    assert verdicts == [False] * len(items), verdicts
    assert device.rlc_stats.rlc_launches >= 1
    print("rlc_smoke: device MSM + pairing tail compiled and bisected")


if __name__ == "__main__":
    sys.exit(main())
