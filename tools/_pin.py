"""Shared CPU-backend pinning for tools/ scripts.

Host-side tools (the at-scale CPU sweeps, the telemetry overhead guard)
measure or exercise the CPU path and must stay there even on a host
with a TPU attached, where JAX would otherwise pick the chip (and hold
it, so nothing else on the host could use it).  Pinning through the jax
config works regardless of how the caller's environment sets
``JAX_PLATFORMS``.  Call it BEFORE anything touches a JAX backend:

    sys.path.insert(0, <repo root>)
    from tools._pin import pin_cpu
    pin_cpu()            # or pin_cpu(devices=8) for a virtual mesh

Chip-facing tools (profile_decode, bench_wire, bench_pallas, the
check_* sweeps) must NOT use this — the chip is their target.
"""

import os


def pin_cpu(devices: int | None = None) -> None:
    if devices is not None:
        flag = f"--xla_force_host_platform_device_count={devices}"
        xf = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in xf:
            os.environ["XLA_FLAGS"] = f"{xf} {flag}".strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
