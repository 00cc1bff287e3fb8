"""Backend/platform selection helpers.

``jax.devices()`` (or any compile) commits the backend irrevocably — after
that, ``jax.config.update("jax_platforms", ...)`` is a no-op. A caller that
wants the CPU (tests, processes that must not hold the chip) therefore pins
it BEFORE the first backend touch; a process that must hold the chip checks,
right after it starts, that it got one (:func:`require_platform`).

A chip belongs to one process at a time: a second process that starts JAX
while another holds the TPU either fails on libtpu's lock or lands on the
CPU. Children that need no device pin the CPU with :func:`force_cpu`.
"""

from __future__ import annotations

import os
import re
import sys
from pathlib import Path

_COUNT_FLAG = "--xla_force_host_platform_device_count"
# Fixed, so that every run of this checkout finds the cache again: the
# directory is part of the cache's key.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_cpu(n_devices: int | None = None) -> None:
    """Pin JAX to the host-CPU platform, optionally as ``n_devices``
    virtual devices. Must run before the first backend use; safe to call
    whether or not jax is already imported. Sets ``JAX_PLATFORMS`` too, so
    processes spawned from this one inherit the pin."""
    flags = os.environ.get("XLA_FLAGS", "")
    if n_devices is not None:
        m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
        if m is None:
            flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
        elif int(m.group(1)) < n_devices:
            # Only widen — an externally-requested larger mesh stands.
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags)
        os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already committed; caller checks device count


_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips: v3, PLC, v4, v5p, v5e, v6e, 7x.
_TPU_PCI_DEVICES = frozenset(
    ("0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076")
)


def tpu_chips_on_host(pci_root: str = "/sys/bus/pci/devices") -> int:
    """TPU chips attached to this host over PCI, read from sysfs without
    starting a JAX backend (so without taking the chip)."""
    n = 0
    for dev in Path(pci_root).glob("*"):
        try:
            vendor = (dev / "vendor").read_text().strip()
            device = (dev / "device").read_text().strip()
        except OSError:
            continue
        n += vendor == _GOOGLE_PCI_VENDOR and device in _TPU_PCI_DEVICES
    return n


def expects_tpu() -> bool:
    """True when a process started now should come up on the TPU: a chip
    is on this host and ``JAX_PLATFORMS`` does not pin another platform."""
    pinned = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if pinned and "tpu" not in pinned.split(","):
        return False
    return tpu_chips_on_host() > 0


def require_platform(expected: str | None = None) -> str:
    """Start the backend and return its platform. Exits the process
    non-zero when it is not ``expected`` — by default ``"tpu"`` wherever
    :func:`expects_tpu` holds — instead of letting it serve from the CPU
    while the chip sits elsewhere."""
    if expected is None and expects_tpu():
        expected = "tpu"
    import jax

    try:
        platform = jax.devices()[0].platform
    except RuntimeError as exc:
        sys.exit(f"sentinel_tpu: no {expected or 'JAX'} backend: {exc}")
    if expected is not None and platform != expected:
        sys.exit(
            f"sentinel_tpu: process {os.getpid()} came up on {platform!r}, "
            f"expected {expected!r}"
        )
    return platform


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is and nothing
    else is set. Otherwise the cache lives in ``<repo>/.jax_cache``. Setting
    ``JAX_ENABLE_COMPILATION_CACHE=false`` (as the tests do) still turns it
    off."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
