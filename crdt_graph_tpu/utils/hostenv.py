"""Host-process environment helpers: integer/float/flag env knobs, and
pinning a process to a virtual CPU mesh.

A process that should stay on the CPU (tests, mesh dry-runs, localhost
test fleets) must not initialise the TPU backend: a chip belongs to one
process at a time.  ``JAX_PLATFORMS=cpu`` plus
``jax.config.update("jax_platforms", "cpu")`` keeps it off;
tests/conftest.py and __graft_entry__ use these helpers for that.

This module must stay importable BEFORE jax backend initialisation: it does
not import jax at module level.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def env_int(name: str, default: int) -> int:
    """One integer env knob, falling back to ``default`` on absence OR
    malformed content — the single parser behind the GRAFT_OPLOG_*
    (serve/engine.py) and GRAFT_FLIGHT_*/GRAFT_OBS_* (obs/flight.py)
    sizing knobs, so a typo'd value degrades to the documented default
    instead of crashing process start."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    """Float twin of :func:`env_int` (GRAFT_OPLOG_HOT_AGE_S and the
    obs/flight.py timing knobs)."""
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def flag_on(name: str, default: str = "1") -> bool:
    """One boolean env flag, logged on every read (the GRAFT_OPSAXIS
    route switch, parallel/opsaxis.py).  ``"0"``, ``"off"`` and the
    empty string mean OFF."""
    import logging
    on = os.environ.get(name, default).lower() not in ("0", "off", "")
    logging.getLogger("crdt_graph_tpu.flags").info(
        "trace-time %s=%d", name, on)
    return on


def scrub_tpu_env(n_devices: int = 8) -> None:
    """Set env so the NEXT backend init lands on an n-device CPU host.

    Safe to call before ``import jax``; callers must still follow up with
    ``jax.config.update("jax_platforms", "cpu")`` after importing jax,
    because plugin registration may rewrite the platform list.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"{_COUNT_FLAG}={n_devices}"
    if want not in flags.split():
        flags = re.sub(_COUNT_FLAG + r"=\d+", "", flags)
        os.environ["XLA_FLAGS"] = (flags + " " + want).strip()


def force_cpu_devices(n_devices: int) -> None:
    """Pin this process to an n-device virtual CPU mesh, rebuilding the
    backend if one already initialised with too few devices."""
    scrub_tpu_env(n_devices)
    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices("cpu")) < n_devices:
        # a backend already initialised with too few devices.  XLA_FLAGS is
        # parsed once per process by the C++ layer, so re-setting it is
        # useless here — use the jax-level device-count config and rebuild
        # the client
        from jax.extend.backend import clear_backends
        clear_backends()
        jax.config.update("jax_num_cpu_devices", n_devices)
        have = len(jax.devices("cpu"))
        if have < n_devices:
            raise RuntimeError(
                f"could not obtain {n_devices} CPU devices (have {have}); "
                "jax_num_cpu_devices rebuild failed")


def require_tpu(what: str) -> list:
    """The devices JAX found, or ``SystemExit`` (non-zero) when they are
    not TPUs: a measurement or smoke path never carries on on the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"{what}: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); refusing to run on it")
    return devs
