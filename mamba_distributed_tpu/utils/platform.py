"""Runtime set-up shared by every entry point.

``JAX_PLATFORMS`` picks the backend by itself; what the entry points
share is where compiled programs are kept.  A 64-layer model takes
minutes to compile, so every CLI calls ``configure_compile_cache()``
first and lands in the same persistent cache.
"""

from __future__ import annotations

import os

# the checkout root (the directory that holds train.py / chip_smoke.py)
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# everything the program builds at run time lives under here (git-ignored)
CACHE_ROOT = os.path.join(REPO_ROOT, ".cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
    this function sets no directory at all, so the cache can be placed
    from outside.  Unset: one fixed directory inside the checkout — the
    path is part of the cache key, so it must never be derived from a
    temp dir, a pid or a time.

    Every program is cached, however quickly it compiled: with JAX's
    default one-second floor an entry whose compile time straddles the
    floor is written on some runs and not on others, and a warm run
    could still add entries.
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(CACHE_ROOT, "jax")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    key_compile_cache_by_scopes()
    return env_dir or jax.config.jax_compilation_cache_dir


def init_backend():
    """Set-up of a tool that exists to run on the chip; returns the device.

    Places the compile cache, brings the backend up and insists on a
    TPU: on any other platform the exit is non-zero, the reason is one
    line on stderr, and nothing has been printed on stdout.
    """
    import jax

    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"needs a TPU: jax.devices()[0].platform is {dev.platform!r}"
        )
    return dev


def key_compile_cache_by_scopes() -> None:
    """Make an operation's names part of the persistent cache's key.

    By default the cache keys a program by its HLO with the debug info
    stripped, and a hit loads the executable *with the names it was
    compiled under*: after a scope is added or renamed
    (``obs/scopes.py``) every device trace would go on showing the old
    ``op_name``s until the cache is emptied (seen on the chip: a train
    step compiled before the scopes existed was found again and 100 % of
    its time read as unscoped).  JAX's own switch for this: the key then
    holds names, files and lines, so a checkout at another path, or an
    edit that moves lines, compiles once more.  ``Trainer`` and
    ``ServingEngine`` call this as they are built, so whoever builds them
    without an entry point (the benchmark) gets traces that can be read.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def describe_devices(mesh=None) -> str:
    """One start-up line: what the process runs on and how much of it.

    ``build_mesh`` takes the first ``num_devices`` devices and says
    nothing, so on a four-chip host the default preset trains on one;
    Trainer and ServingEngine print this line so that is visible.
    """
    import jax

    present = jax.devices()
    dev = present[0] if mesh is None else mesh.devices.flat[0]
    used = 1 if mesh is None else mesh.devices.size
    shape = "none" if mesh is None else dict(mesh.shape)
    return (
        f"platform {dev.platform} | device_kind {dev.device_kind} | "
        f"devices {used} of {len(present)} | mesh {shape}"
    )
