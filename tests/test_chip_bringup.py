"""Chip bring-up contracts that need no chip and compile no pairing:
strict device selection, one owner per chip, the one compile-cache helper,
and the entry points that must FAIL without a TPU (chip_smoke.py,
benchmark/run.py).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from handel_tpu.ops import fp
from handel_tpu.utils import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, drop=(), timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    env["PYTHONPATH"] = REPO
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )


# -- entry points without a chip ---------------------------------------------


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_fails_at_once_without_tpu(argv):
    """JAX_PLATFORMS=cpu: non-zero exit, `"ok": false` on the last line, and
    nothing built or compiled (it stops at the device line)."""
    r = _run(["chip_smoke.py", *argv], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and "no TPU" in last["error"]
    phases = [json.loads(l).get("phase") for l in lines[:-1]]
    assert phases == ["device", "failed"], phases


def _cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_benchmark_run_refuses_without_tpu(cell, tmp_path):
    """benchmark/run.py under JAX_PLATFORMS=cpu: exit 1 at the device line,
    before any key is generated, and no result line — a time from another
    backend is not a measurement."""
    r = _run(
        ["benchmark/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1"],
        {"JAX_PLATFORMS": "cpu",
         "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert r.returncode == 1
    lines = r.stdout.strip().splitlines()
    assert lines[-1].startswith("run failed, no result")
    assert "JAX found no TPU" in lines[-1]
    phases = [json.loads(l).get("phase") for l in lines[:-1]]
    assert phases == ["device"], phases


# -- the compile-cache helper ------------------------------------------------

_CACHE_PROBE = (
    "from handel_tpu.utils.jaxenv import enable_compile_cache; import jax; "
    "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)"
)


def test_compile_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets no directory:
    JAX's own reading of the variable stands."""
    want = str(tmp_path / "elsewhere")
    r = _run(["-c", _CACHE_PROBE], {"JAX_COMPILATION_CACHE_DIR": want})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]


def test_compile_cache_default_is_one_fixed_checkout_path(tmp_path):
    """Unset: the same git-ignored in-checkout path from two processes,
    whatever their TMPDIR/HOME/pid."""
    outs = []
    for i in range(2):
        r = _run(
            ["-c", _CACHE_PROBE],
            {"TMPDIR": str(tmp_path / f"t{i}"), "HOME": str(tmp_path / f"h{i}")},
            drop=("JAX_COMPILATION_CACHE_DIR",),
        )
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.split())
    want = os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), "cache must be git-ignored"


def test_one_helper_sets_the_cache_dir():
    """No other code path sets a cache directory."""
    repo = pathlib.Path(REPO)
    sources = [*repo.glob("*.py")] + [
        p for d in ("handel_tpu", "scripts", "tests")
        for p in (repo / d).rglob("*.py")
    ]
    hits = sorted(
        str(p.relative_to(repo))
        for p in sources
        if p != pathlib.Path(__file__).resolve()
        and '"jax_compilation_cache_dir"' in p.read_text()
    )
    assert hits == ["handel_tpu/utils/jaxenv.py"]


# -- platform selection ------------------------------------------------------


def test_platform_env_is_only_jax_platforms(monkeypatch):
    monkeypatch.setenv("HANDEL_TPU_PLATFORM", "cpu")
    assert jaxenv.apply_platform_env() == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    monkeypatch.setenv("HANDEL_TPU_PLATFORM", "gpu")
    with pytest.raises(ValueError, match="cpu or tpu"):
        jaxenv.apply_platform_env()


def test_platform_selected_too_late_is_an_error(monkeypatch):
    """jax is imported (conftest) with jax_platforms=cpu: asking for the
    TPU now cannot take effect and must not pretend to."""
    monkeypatch.setenv("HANDEL_TPU_PLATFORM", "tpu")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(RuntimeError, match="before the first `import jax`"):
        jaxenv.apply_platform_env()
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_unknown_accelerator_is_an_error(monkeypatch):
    """Decided from the platform, nothing swallowed: anything but cpu/tpu
    raises instead of being taken for a TPU."""
    decide = fp.device_platform.__wrapped__  # past the once-only cache
    assert decide() == "cpu"
    monkeypatch.setattr(fp.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="unsupported JAX platform 'gpu'"):
        decide()

    def boom():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(fp.jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        decide()


# -- one owner per chip ------------------------------------------------------


@pytest.mark.parametrize(
    "env,owners,refused",
    [
        ({"HANDEL_TPU_PLATFORM": "tpu"}, 2, True),
        ({"JAX_PLATFORMS": "tpu"}, 4, True),
        ({}, 2, True),  # JAX's default: the chip where one is attached
        ({"HANDEL_TPU_PLATFORM": "tpu"}, 1, False),
        ({"HANDEL_TPU_PLATFORM": "cpu"}, 4, False),
        ({"JAX_PLATFORMS": "cpu"}, 2, False),
    ],
)
def test_check_one_chip_owner(monkeypatch, env, owners, refused):
    monkeypatch.delenv("HANDEL_TPU_PLATFORM", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    if refused:
        with pytest.raises(RuntimeError, match="one chip belongs to one"):
            jaxenv.check_one_chip_owner(owners, "test")
    else:
        jaxenv.check_one_chip_owner(owners, "test")


_REFUSAL_PROBE = """
import asyncio, sys
from handel_tpu.sim.config import RunConfig, SimConfig
from handel_tpu.sim import platform as P

async def no_children(*a, **k):
    raise AssertionError("a child process was started")

asyncio.create_subprocess_exec = no_children
cfg = SimConfig(network="udp", scheme="bn254-jax", max_timeout_s=5.0,
                runs=[RunConfig(nodes=4, threshold=3, processes=2)])
try:
    asyncio.run(P.LocalhostPlatform(cfg, sys.argv[1]).start_run(0))
except RuntimeError as e:
    print("REFUSED:", e)
print("jax imported:", "jax" in sys.modules)
"""


def test_localhost_device_run_with_two_owners_refused_before_any_child(tmp_path):
    """scheme bn254-jax, processes = 2, platform tpu: refused at start with
    the reason, no child spawned — and the parent did keygen on the host
    scheme without ever importing jax."""
    r = _run(
        ["-c", _REFUSAL_PROBE, str(tmp_path)],
        {"HANDEL_TPU_PLATFORM": "tpu"}, drop=("JAX_PLATFORMS",),
    )
    assert r.returncode == 0, r.stderr
    assert "REFUSED: localhost platform: 2 processes" in r.stdout
    assert "one chip belongs to one process" in r.stdout
    assert "jax imported: False" in r.stdout
    assert os.path.exists(tmp_path / "registry_0.csv")  # keygen did run


def test_keygen_scheme_is_the_host_scheme():
    from handel_tpu.models.bls12_381 import BLS12381Scheme
    from handel_tpu.models.bn254 import BN254Scheme
    from handel_tpu.models.fake import FakeScheme
    from handel_tpu.models.registry import new_keygen_scheme

    assert type(new_keygen_scheme("bn254-jax")) is BN254Scheme
    assert type(new_keygen_scheme("bn256-tpu")) is BN254Scheme
    assert type(new_keygen_scheme("bls12-381-jax")) is BLS12381Scheme
    assert type(new_keygen_scheme("bn254")) is BN254Scheme
    assert type(new_keygen_scheme("fake")) is FakeScheme
    with pytest.raises(ValueError, match="unknown signature scheme"):
        new_keygen_scheme("nope")
