"""Backend/platform selection, the compile-cache policy, and the
platform-auto knob resolvers.

Stock ``JAX_PLATFORMS`` selects the backend. ``force_platform`` exists for
what the env var cannot do after JAX is imported: switch an in-process
backend (``GAMESMAN_PLATFORM``) and fake N host devices for the CPU test
mesh (tests/conftest.py).
"""

from __future__ import annotations

import os
import pathlib

from gamesmanmpi_tpu.utils.env import env_opt, env_str

#: Default persistent compile-cache directory (see use_compile_cache).
REPO_CACHE_DIR = (
    pathlib.Path(__file__).resolve().parents[2] / ".jax_compile_cache")

# Bumped every time force_platform actually clears initialized backends.
# Kernel caches (solve/engine.py _cache_key) mix this into their keys:
# executables closed over pre-clear device/Mesh objects would otherwise be
# reused after a clear and die with "incompatible devices for jitted
# computation" (the exact failure the full suite hit when every in-process
# CLI run re-forced an already-active CPU backend).
_BACKEND_EPOCH = 0


def backend_epoch() -> int:
    return _BACKEND_EPOCH


def force_platform(platform: str, fake_devices: int | None = None) -> None:
    """Select a JAX platform robustly; optionally fake N host devices.

    Must run before the first jax array/device operation for the XLA_FLAGS
    part to take effect. No-op (beyond config settles) when the requested
    platform is already the active backend — clearing live backends orphans
    every cached executable keyed on their device objects. If a genuine
    switch is needed and backends are initialized, they are cleared and the
    backend epoch is bumped (pre-existing arrays keep their original
    backend; epoch-keyed kernel caches rebuild lazily).
    """
    flags_changed = False
    if fake_devices is not None and platform == "cpu":
        flags = env_str("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={fake_devices}"
            ).strip()
            flags_changed = True

    import jax

    # Config first: clearing/initializing backends re-reads the config.
    jax.config.update("jax_platforms", platform)
    jax.config.update("jax_enable_x64", True)

    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return

    if not flags_changed:
        # Already initialized: if the active default backend IS the
        # requested platform, clearing would only poison kernel caches.
        # (flags_changed means the device count just changed, so the
        # existing CPU backend is stale and must be rebuilt regardless.)
        try:
            current = jax.default_backend()
        except Exception:  # pragma: no cover - backend probe never raised
            current = None
        if current == platform:
            return

    from jax.extend.backend import clear_backends

    clear_backends()
    global _BACKEND_EPOCH
    _BACKEND_EPOCH += 1


def apply_platform_env(default_fake_devices: int | None = None) -> None:
    """Honor GAMESMAN_PLATFORM (and GAMESMAN_FAKE_DEVICES) if set."""
    platform = env_opt("GAMESMAN_PLATFORM")
    if not platform:
        return
    fake = env_opt("GAMESMAN_FAKE_DEVICES")
    fake_devices = int(fake) if fake else default_fake_devices
    force_platform(platform, fake_devices)


def pin_host_cpu() -> None:
    """Keep this process off the accelerator: the serving kernels are
    host-side by design (db/reader.py), so N serve/query workers must
    never load the TPU runtime. Takes effect before the first backend
    init; a process that already holds a backend keeps it (the reader
    places its kernels on the CPU device explicitly)."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        jax.config.update("jax_platforms", "cpu")


def use_compile_cache() -> None:
    """Place JAX's persistent compilation cache, the one policy for every
    entry point (CLI, bench, chip_smoke.py).

    A cache directory given from outside (``JAX_COMPILATION_CACHE_DIR``,
    which JAX reads itself, or a config already set in-process) wins and
    nothing is set here. Otherwise the cache lives at the fixed path
    ``<repo root>/.jax_compile_cache`` (gitignored): the path is part of
    the cache key, so a directory that moved would never hit.
    """
    import jax

    if env_opt("JAX_COMPILATION_CACHE_DIR") or \
            jax.config.jax_compilation_cache_dir:
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))


def platform_auto_flag(name: str, accel: str, cpu: str,
                       choices: tuple[str, ...]) -> str:
    """Resolve an env knob with platform-auto default, strictly.

    Reads os.environ[name]; "auto"/unset resolves to `accel` on
    accelerators and `cpu` on the CPU backend (decided at call time — the
    kernel builders call this at cache-key time). Any other value must be
    in `choices`; unknown values raise instead of silently measuring the
    auto default — these knobs exist for chip A/B runs, where a typo that
    falls back to auto records two identical configurations.
    """
    raw = env_str(name, "auto")
    if raw in choices:
        return raw
    if raw != "auto":
        raise ValueError(
            f"{name}={raw!r}: expected one of {('auto',) + choices}"
        )
    import jax

    return accel if jax.default_backend() != "cpu" else cpu


def platform_auto_bool(name: str, accel: bool, cpu: bool) -> bool:
    """Boolean twin of platform_auto_flag ("1"/"on"/"true", "0"/"off"/
    "false", "auto"/unset; anything else raises)."""
    on, off = ("1", "on", "true"), ("0", "off", "false")
    raw = env_str(name, "auto").lower()
    if raw in on:
        return True
    if raw in off:
        return False
    if raw != "auto":
        raise ValueError(
            f"{name}={raw!r}: expected auto, {'/'.join(on)} or "
            f"{'/'.join(off)}"
        )
    import jax

    return accel if jax.default_backend() != "cpu" else cpu
