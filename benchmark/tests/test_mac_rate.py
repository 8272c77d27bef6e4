"""`fp_mul.mac_rate` on hand-made reductions and on the launch recorded on
the chip (tests/data/README.md): limb multiply-adds of the Mosaic
multiplications over their self time."""

import gzip
import os
import shutil
from types import SimpleNamespace

import pytest

import trace_reduce as tr
from readers import trace_mac_rate

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CALL = ('%fp_mul_{n}x{b}.{k} = u32[{n},{b}]{{1,0:T(8,128)S(1)}} custom-call(...), '
        'custom_call_target="tpu_custom_call"')


def _ctx(ops):
    """ops: (name, executions, self ns) -> a reader context of one plane."""
    plane = tr.PlaneReduction(
        "/device:TPU:0",
        self_ns={n: ns for n, _, ns in ops}, count={n: c for n, c, _ in ops})
    return SimpleNamespace(trace=tr.Reduction(1e9, 0, 1e9, [plane], None))


def test_macs():
    assert trace_mac_rate.macs(16, 128) == 2 * 256 * 128
    assert trace_mac_rate.macs(24, 128) == 2.25 * trace_mac_rate.macs(16, 128)


def test_both_fields_one_unit():
    bn = CALL.format(n=16, b=13824, k=1)
    bls = CALL.format(n=24, b=6912, k=2)
    ctx = _ctx([(bn, 10, 1e6), (bls, 4, 3e6)])
    want = (10 * 2 * 16 * 16 * 13824 + 4 * 2 * 24 * 24 * 6912) / 4e6
    assert trace_mac_rate.read(ctx) == pytest.approx(want)


def test_other_calls_left_out_of_both_sums():
    mul = CALL.format(n=24, b=128, k=1)
    rns = ('%rns_mul_70x6912.3 = s32[70,6912]{1,0} custom-call(...), '
           'custom_call_target="tpu_custom_call"')
    fusion = "%fusion.7 = u32[24,128]{1,0} fusion(...), kind=kLoop"
    alone = trace_mac_rate.read(_ctx([(mul, 5, 1e5)]))
    mixed = trace_mac_rate.read(_ctx(
        [(mul, 5, 1e5), (rns, 9, 1e6), (fusion, 9, 1e6)]))
    assert alone == mixed == pytest.approx(5 * 2 * 576 * 128 / 1e5)


def test_nothing_to_read():
    assert trace_mac_rate.read(SimpleNamespace(trace=None)) is None
    assert trace_mac_rate.read(_ctx([("%fusion.7 = u32[16,128]{1,0} fusion(", 3, 1e6)])) is None


def test_recorded_launch(tmp_path):
    """One `jit_verify_range8` of BN254 recorded on the chip in PR 25: 127e9
    limb multiply-adds a second over the 57.6 % of the launch spent in them."""
    dst = tmp_path / "chip_small.xplane.pb"
    with gzip.open(os.path.join(DATA, "chip_small.xplane.pb.gz"), "rb") as f, \
            open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    red = tr.reduce_trace(str(dst))
    rate = trace_mac_rate.read(SimpleNamespace(trace=red))
    assert rate == pytest.approx(127.35482954948816, rel=1e-9)
    # the same number by hand from the grouped names
    done = ns = 0
    for name, s in red.planes[0].self_ns.items():
        g = tr.op_group(name)
        if g.startswith("custom-call:tpu_custom_call u32[16,"):
            lanes = int(g.split(",")[1].rstrip("]"))
            done += 512 * lanes * red.planes[0].count[name]
            ns += s
    assert rate == pytest.approx(done / ns)
