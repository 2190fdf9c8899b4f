"""Per-backend XLA setup — the knobs that make measured timings honest.

Timing a collective under XLA only measures what the paper measures if
the compiler is actually allowed to run collectives the way the cost
model assumes: asynchronously, with the latency-hiding scheduler free
to overlap them with compute.  On GPU those are opt-in flags; on CPU
the multi-device topology itself is a flag
(``--xla_force_host_platform_device_count``).  Scattering these across
entry points is how benchmarks silently measure the wrong thing, so
this module owns them as one tested surface: every probe/bench/test
entry point calls :func:`apply_backend_setup` BEFORE its first jax
import, and nothing else touches ``XLA_FLAGS``.

``merge_xla_flags`` is idempotent and override-last: re-running setup
in the same process (or under a harness that pre-seeds XLA_FLAGS)
keeps user-provided flags it does not own and replaces stale values of
the ones it does.
"""
from __future__ import annotations

import os
import sys
from typing import MutableMapping, Optional

__all__ = [
    "GPU_XLA_FLAGS", "xla_flags_for", "merge_xla_flags",
    "apply_backend_setup", "HOST_DEVICE_COUNT_FLAG",
]

HOST_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

# async collectives + the latency-hiding scheduler are the two GPU
# prerequisites of the §5 overlap story; combine-threshold 0 keeps XLA
# from re-fusing the bucketed grad-sync back into one monolithic
# allreduce (which would erase exactly the structure being timed)
GPU_XLA_FLAGS = {
    "--xla_gpu_enable_async_collectives": "true",
    "--xla_gpu_enable_latency_hiding_scheduler": "true",
    "--xla_gpu_all_reduce_combine_threshold_bytes": "0",
}


def xla_flags_for(platform: str, *,
                  host_device_count: Optional[int] = None) -> dict:
    """The XLA flag dict this project owns for ``platform``.

    cpu: the forced host-platform device count (when requested) — the
    only way a single host presents a multi-chip topology to probe.
    gpu: the async-collective/scheduler set above.  tpu: nothing — the
    defaults already run collectives asynchronously.
    """
    platform = platform.lower()
    flags: dict = {}
    if platform == "cpu":
        if host_device_count is not None:
            flags[HOST_DEVICE_COUNT_FLAG] = str(int(host_device_count))
    elif platform == "gpu":
        flags.update(GPU_XLA_FLAGS)
    elif platform != "tpu":
        raise ValueError(f"unknown platform {platform!r} "
                         f"(expected cpu/gpu/tpu)")
    return flags


def merge_xla_flags(existing: str, flags: dict) -> str:
    """Merge ``flags`` into an XLA_FLAGS string, override-last.

    Tokens in ``existing`` whose ``--key`` is owned by ``flags`` are
    dropped (ours win); everything else is preserved in order.  Running
    the merge twice with the same flags is a no-op — entry points may
    call setup unconditionally.
    """
    owned = set(flags)
    kept = [tok for tok in existing.split()
            if tok.split("=", 1)[0] not in owned]
    kept.extend(f"{k}={v}" for k, v in flags.items())
    return " ".join(kept)


def apply_backend_setup(platform: str, *,
                        host_device_count: Optional[int] = None,
                        env: Optional[MutableMapping] = None) -> str:
    """Install this project's XLA flags for ``platform`` into
    ``env["XLA_FLAGS"]`` (default ``os.environ``) and return the final
    string.  MUST run before the process's first jax backend use —
    XLA_FLAGS is read once at backend initialization; changing it
    afterwards silently does nothing.

    ``"cpu"`` also pins the platform (``JAX_PLATFORMS=cpu``): a CPU entry
    point that simulates several devices must not take a TPU that happens
    to be attached, see one device and fail in its mesh code.
    """
    if env is None:
        env = os.environ
    merged = merge_xla_flags(
        env.get("XLA_FLAGS", ""),
        xla_flags_for(platform, host_device_count=host_device_count))
    env["XLA_FLAGS"] = merged
    if platform.lower() == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        if env is os.environ and "jax" in sys.modules:
            # jax read JAX_PLATFORMS when it was imported
            sys.modules["jax"].config.update("jax_platforms", "cpu")
    return merged
