"""What a measuring entry point ran on, and whether the device path held.

Shared by `python -m kubernetes_tpu.perf`, chip_smoke.py, benchmark/run.py's
drivers and the binaries' `--platform` flag so that no number (and no ready
line) is labelled with a device that did not produce it: the platform comes from `jax.devices()`, never from an
environment variable, and a run during which the device-path circuit
breaker was charged (models/tpu_scheduler.py `_note_device_failure` — the
pods were rescheduled on the host Evaluator) is a failed measurement even
though scheduling completed.
"""

from __future__ import annotations

import os
from typing import Dict


def device_info() -> Dict[str, object]:
    """The backend as JAX reports it (initializes it: on a chip machine the
    calling process owns the chip from here on)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def pin_platform(choice: str) -> str:
    """Apply a binary's `--platform` choice; call before anything touches
    the backend. `cpu` pins the host backend, `auto` takes what JAX finds,
    `tpu` means it: the returned error message is non-empty when JAX's
    backend is anything else, and the binary exits before its ready line."""
    import jax

    if choice == "cpu":
        jax.config.update("jax_platforms", "cpu")
    if choice == "tpu" and jax.default_backend() != "tpu":
        return (f"--platform tpu: JAX found no TPU (backend "
                f"{jax.default_backend()!r}, devices {jax.devices()})")
    return ""


def measuring_device() -> Dict[str, object]:
    """device_info() for a run that will print a rate. Anything but a TPU
    is refused unless the CPU was asked for BY NAME (JAX_PLATFORMS=cpu) —
    then the result says `cpu` in its platform field. There is no probing
    and no fallback: with no chip and no such request JAX itself fails at
    backend init, or this raises."""
    info = device_info()
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if info["platform"] != "tpu" and "cpu" not in asked:
        raise SystemExit(
            f"no TPU: jax.devices() reports {info['platform']!r} "
            f"({info['kind']}); set JAX_PLATFORMS=cpu to run on the CPU "
            "by name")
    return info


def fallbacks_by_reason(sched) -> Dict[str, int]:
    """`scheduler_device_path_fallback_total` of an in-process scheduler as
    {reason: count} (empty for a host-only scheduler's untouched series)."""
    return {key[0]: int(n) for key, n in
            sched.metrics.device_path_fallback._values.items()}


def breaker_charges(fallbacks: Dict[str, float]) -> Dict[str, int]:
    """The {reason: count} entries that charged the breaker. `unsupported`
    is the designed host route for pods the kernel does not cover, not a
    device failure."""
    return {reason: int(n) for reason, n in fallbacks.items()
            if n and reason != "unsupported"}
