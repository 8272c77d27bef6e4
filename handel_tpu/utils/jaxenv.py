"""Process-level JAX set-up: platform selection and the compile cache.

One chip belongs to one process. The process that will own it calls
`apply_platform_env()` before JAX initialises a backend and
`enable_compile_cache()` before its first compile; a parent that only
spawns chip owners (sim/platform.py, sim/remote.py, the scripts that
`subprocess` a sim run) calls neither and never touches JAX.

Knob: HANDEL_TPU_PLATFORM=cpu|tpu is copied into JAX_PLATFORMS, nothing
more. Unset/empty = leave JAX's own default alone (the TPU where one is
attached, and a start-up failure where one is expected but missing).

Compile cache: pairing-sized graphs take minutes cold. When
JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and this module sets
no directory; otherwise the cache lives at one fixed, git-ignored path
inside the checkout (`CACHE_DIR`) — the path is part of the cache key, so
it must not move between processes or runs.
"""

from __future__ import annotations

import os
import sys

PLATFORMS = ("cpu", "tpu")

# <checkout>/.jax_cache: fixed for every process of this checkout
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def apply_platform_env(
    default: str | None = None, force_host_device_count: int | None = None
) -> str:
    """Copy $HANDEL_TPU_PLATFORM (or `default`) into JAX_PLATFORMS.

    Environment only — JAX is not imported here, so a parent process may
    call this for its children without ever owning a device. Must run
    before JAX initialises its backends; raises if it is too late to take
    effect. Returns the selected platform ("" = JAX's default).

    force_host_device_count: also expose that many virtual devices on the
    host platform (the 8-device CPU mesh used by tests and dryrun). It only
    affects the HOST cpu platform, so it is harmless on TPU runs.
    """
    if force_host_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={force_host_device_count}"
            ).strip()
    plat = os.environ.get("HANDEL_TPU_PLATFORM", "") or default or ""
    if not plat:
        return ""
    if plat not in PLATFORMS:
        raise ValueError(
            f"HANDEL_TPU_PLATFORM={plat!r}: this installation runs on "
            f"{' or '.join(PLATFORMS)}"
        )
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_platforms != plat:
        # JAX read JAX_PLATFORMS when it was imported; the variable set
        # below would be ignored by this process
        raise RuntimeError(
            f"platform {plat!r} requested after jax was imported with "
            f"jax_platforms={jax.config.jax_platforms!r}: select the "
            "platform before the first `import jax`"
        )
    os.environ["JAX_PLATFORMS"] = plat
    return plat


def selected_platform() -> str:
    """The platform a process started now would run on, from the
    environment alone (no JAX import): HANDEL_TPU_PLATFORM, else the first
    entry of JAX_PLATFORMS, else "" (JAX's default — the chip where one is
    attached)."""
    plat = os.environ.get("HANDEL_TPU_PLATFORM", "")
    if not plat:
        plat = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return plat.strip().lower()


def check_one_chip_owner(owners: int, what: str) -> None:
    """Refuse a run that would start more than one chip-owning process on
    this host. A TPU belongs to one process at a time: the second owner
    fails or hangs at device init, long after the run started. Called by
    the platforms before any child is spawned. CPU runs are unaffected."""
    if owners > 1 and selected_platform() != "cpu":
        raise RuntimeError(
            f"{what}: {owners} processes would each build a device "
            f"verifier on platform {selected_platform() or 'tpu (default)'}, "
            "but one chip belongs to one process — the others fail or hang "
            "at device init. Use processes = 1 (one process hosts every "
            "node and drives the chip), or select HANDEL_TPU_PLATFORM=cpu."
        )


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process and return
    its directory. The ONE place that may set `jax_compilation_cache_dir`:
    with JAX_COMPILATION_CACHE_DIR in the environment JAX has already
    taken that directory and nothing is set here."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
