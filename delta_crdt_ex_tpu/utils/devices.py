"""Device topology helpers: virtual CPU devices for tests, the fleet
mesh, and the persistent compilation cache.

Multi-chip shardings are validated without TPU hardware by running on
``n`` virtual CPU devices. ``XLA_FLAGS`` is parsed once at FIRST backend
initialisation, so the device count must be forced before any
``jax.devices()`` call.
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"


def forced_device_count() -> int | None:
    """The virtual host-device count requested via ``XLA_FLAGS``, or
    None when the flag is absent — the public read-side of the flag this
    module owns (callers must not parse ``XLA_FLAGS`` themselves)."""
    m = re.search(rf"--{_FLAG}=(\d+)", os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else None


def backend_initialised(default: bool = True) -> bool:
    """Whether any XLA backend has been created in this process.

    Probes a jax-internal cache; ``default`` is returned if the private
    API moves in a future jax version.
    """
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        return default


def force_cpu_devices(n: int) -> None:
    """Force jax onto the CPU platform with ``n`` virtual devices.

    Must run before the first backend initialisation: XLA parses the
    host-device count exactly once, so afterwards the count cannot
    change in-process (jax itself raises on the config update) and this
    raises rather than silently yielding the wrong mesh size.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags:
        flags = re.sub(rf"--{_FLAG}=\d+", rf"--{_FLAG}={n}", flags)
    else:
        flags = f"{flags} --{_FLAG}={n}".strip()
    os.environ["XLA_FLAGS"] = flags
    # the env var pins subprocesses; the config update pins this process
    # even where jax was imported before the env var was set
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    if not backend_initialised(default=True):  # unknown — verify via reset
        return

    # A backend may predate the settings above: reset and re-check. XLA
    # parses the host-device count once per process, so if a previous
    # backend consumed the old value the recreated one keeps it — raise
    # rather than hand back a silently wrong mesh size.
    import jax.extend.backend

    jax.extend.backend.clear_backends()
    devices = jax.devices()
    if len(devices) < n or devices[0].platform != "cpu":
        raise RuntimeError(
            f"force_cpu_devices({n}) called after the XLA backend was already "
            f"initialised ({len(devices)} {devices[0].platform} device(s)); "
            "the host device count is parsed once per process. Call this "
            "before any jax operation (or run in a fresh process)."
        )


def detected_topology() -> dict:
    """The detected device/mesh shape, in the exact field vocabulary the
    ``test_multihost_spmd`` capability probe prints (``PROBE_SHAPE``):
    platform, global/local device counts, process count. Recorded into
    ``Fleet.stats()`` and every benchmark artifact so CPU-vs-TPU
    evidence under ``benchmarks/results/`` is self-describing.

    Initialises the XLA backend if nothing has yet (callers that need
    a forced device count must call :func:`force_cpu_devices` first).
    """
    import jax

    return {
        "platform": jax.default_backend(),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "processes": jax.process_count(),
    }


def mesh_shard_count(n_devices: int | None = None) -> int:
    """Largest power-of-two shard count the detected (or given) device
    count supports — the default width of :func:`fleet_mesh`. Power-of-
    two only: the fleet's lane tiers are pow2, and lanes must divide
    evenly into shards for the ``shard_map`` lift."""
    if n_devices is None:
        import jax

        n_devices = len(jax.devices())
    if n_devices < 1:
        raise ValueError("no devices detected")
    return 1 << (int(n_devices).bit_length() - 1)


def fleet_mesh(shards: int | None = None, devices=None):
    """A 1-D ``Mesh(devices[:shards], ("replicas",))`` for
    ``Fleet(mesh=...)`` / ``start_fleet(..., mesh=...)`` (ISSUE 13).

    ``shards`` defaults to the largest power of two the detected device
    set supports (:func:`mesh_shard_count`); non-pow2 counts raise —
    the fleet pads replica-lane tiers to pow2, and the lane axis must
    split evenly across shards. On CPU the mesh is exercisable by
    forcing virtual devices (:func:`force_cpu_devices`, honouring
    ``--xla_force_host_platform_device_count``), which is how tier-1
    drives the whole plane without a TPU."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    if shards is None:
        shards = mesh_shard_count(len(devices))
    shards = int(shards)
    if shards < 1 or shards & (shards - 1):
        raise ValueError(f"mesh shard count must be a power of two: {shards}")
    if shards > len(devices):
        raise ValueError(
            f"{shards} shards requested but only {len(devices)} device(s) "
            f"detected (force more with force_cpu_devices on CPU)"
        )
    return Mesh(_np.array(devices[:shards]), ("replicas",))


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache so repeated runs reuse
    compiled executables. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already keeps its cache there and this sets nothing; otherwise
    the cache goes to the fixed ``<repo>/.jax_cache`` (a fixed path,
    because the path is part of the cache key). Returns the cache
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        ".jax_cache",
    )
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    return path
