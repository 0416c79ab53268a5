"""Execution-platform probing (ROADMAP: platform-derived runtime defaults).

``RuntimeConfig`` used to hard-code ``interpret=True`` — right for CPU hosts
(Pallas kernels only run there in interpret mode) and silently wrong on a real
TPU/GPU, where every ``--use-pallas`` launch needed a manual
``runtime_overrides(interpret=False)``.  This module asks JAX what it is
actually running on, once, and the answers become the config defaults.

Probes are cached (the backend cannot change within a process) and raise
whatever JAX raises: a backend that fails to initialize is an error, never a
silent answer of "cpu" that would turn Pallas interpret mode on behind the
caller's back.

:func:`enable_compile_cache` places JAX's persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX uses that directory, otherwise a
fixed directory inside the checkout.
"""
from __future__ import annotations

import os
from functools import lru_cache
from pathlib import Path
from typing import Dict

# Backends where the Pallas kernels compile for real hardware; anything else
# (cpu, interpreters, mocks) needs interpret mode.
_ACCELERATOR_BACKENDS = frozenset({"tpu", "gpu", "cuda", "rocm"})

# The checkout-local cache directory (listed in .gitignore).  A fixed path:
# the cache key includes it, so a directory that moves never hits.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


@lru_cache(maxsize=None)
def backend() -> str:
    """The active JAX backend name ("cpu", "gpu", "tpu")."""
    import jax

    return jax.default_backend()


@lru_cache(maxsize=None)
def device_kind() -> str:
    """Hardware kind of device 0 (e.g. "cpu", "TPU v5 lite")."""
    import jax

    return jax.devices()[0].device_kind


@lru_cache(maxsize=None)
def device_count() -> int:
    """Number of addressable local devices.  Forced host platforms
    (``--xla_force_host_platform_device_count``) count — that is exactly how
    the lane tests exercise ``shard_map`` on CPU."""
    import jax

    return jax.local_device_count()


def lanes_backend(num_lanes: int) -> str:
    """How the sharded pipeline should run its parallel lanes on this host:
    ``"shard_map"`` when one device per lane exists (each lane's tracker bank
    lives on its own device, the paper's multi-bank memory fabric),
    ``"vmap"`` otherwise (single-device hosts batch the lanes — for the scan
    tracker this still cuts the serial depth to the per-lane capacity)."""
    return "shard_map" if 1 < num_lanes <= device_count() else "vmap"


def is_accelerator() -> bool:
    """True when running on a real TPU/GPU backend (not host emulation)."""
    return backend() in _ACCELERATOR_BACKENDS


def interpret_default() -> bool:
    """Platform-correct ``RuntimeConfig.interpret``: Pallas interpret mode is
    required on CPU hosts and wrong (slow, and unsupported ops) on real
    accelerators."""
    return not is_accelerator()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets no other path.  Otherwise the cache lives at
    :data:`CHECKOUT_CACHE_DIR`.  Every compile is cached, however short.
    Call before the first compilation of the process."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def fingerprint() -> Dict[str, str]:
    """Identity of the execution platform, embedded in calibration artifacts
    so a cache written on one target is never silently applied to another."""
    import jax

    return {
        "backend": backend(),
        "device_kind": device_kind(),
        "jax": jax.__version__,
    }


def fingerprint_id(fp: Dict[str, str] | None = None) -> str:
    """Short one-line form of :func:`fingerprint` ("cpu/cpu/jax-0.9.0")."""
    fp = fp if fp is not None else fingerprint()
    return f"{fp['backend']}/{fp['device_kind']}/jax-{fp['jax']}"
