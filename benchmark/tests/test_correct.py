"""The comparison that decides `correct` can fail.

  * the CONTROL: the reference in the program's place with one stated
    guarantee broken comes out not correct, and with none broken, correct
    (at a pool a test run can hold; the chip-size runs are in PERF.md);
  * the timed path broken underneath: a whole `run.py --rehearse` run (the
    look for a chip skipped, nothing else) in which the engine's `fetch`
    alters one verdict where it is produced sees `correct` come out false.
"""

import argparse
import json

import pytest

import control
import spec

CELLS = ["handel4096-99thr.closed256", "handel4096-51thr-failing.closed256",
         "handel4096-51thr-failing.mixed-levels"]  # the last: a pool held to its classes


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault", control.FAULTS)
def test_control(cell_name, fault):
    cell = spec.Cell(cell_name)
    checks, info = control.run_control(
        cell, seed=2**31 + 77, seconds=0.5, fault=fault,
        pool_requests=64, clients=16,
    )
    assert info["sample_forged"] >= 1
    failed = [c.name for c in checks if not c.ok]
    if fault == "sound":
        assert not failed
    else:
        assert "verdicts_differing_from_reference" in failed
        assert "verdicts_differing_from_construction" in failed


def rehearse(capsys, cell_name="handel4096-99thr.closed256"):
    import run

    args = argparse.Namespace(
        workload=cell_name, seed=2**31 + 3, seconds=3.0, trace=0,
        rehearse=True, benchmark="", trace_summary="", slice_seconds=[10.0],
    )
    result, checks = run.run(args)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    rehearsal = next(
        json.loads(l) for l in lines if json.loads(l).get("phase") == "rehearsal"
    )
    return result, checks, rehearsal


@pytest.mark.slow
def test_sound_path_would_be_correct(capsys):
    result, checks, rehearsal = rehearse(capsys)
    assert rehearsal["would_be_correct"] is True
    assert result["correct"] is False and result["metrics"] == {}


@pytest.mark.slow
def test_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """One verdict in sixteen altered where the engine hands it back."""
    from handel_tpu.models.bn254_jax import BN254Device

    sound, calls = BN254Device.fetch, []

    def fetch(self, handle):
        out = sound(self, handle)
        calls.append(len(out))
        if len(calls) > 3 and len(calls) % 4 == 0:  # after the warm launches
            out[0] = not out[0]
        return out

    monkeypatch.setattr(BN254Device, "fetch", fetch)
    result, checks, rehearsal = rehearse(capsys)
    assert rehearsal["would_be_correct"] is False
    failed = {c.name for c in checks if not c.ok}
    assert "verdicts_differing_from_construction" in failed
    assert "verdicts_differing_from_reference" in failed
