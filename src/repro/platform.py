"""Process-level platform configuration for the serving stack.

One module owns every knob that must be set **before** the jax backend
initializes — platform selection, GPU XLA performance flags, emulated
host-device counts, the persistent compilation cache, x64/debug toggles
— so launchers, benchmarks, and CI stop growing their own
``os.environ`` handling (the pattern follows bayespec's
``elisa/util/config.py``).  Everything here is idempotent and safe to
call repeatedly; the functions that *must* precede backend
initialization say so and fail loudly when called too late.  Nothing
here runs at import.

Typical launcher preamble::

    from repro import platform as pf

    pf.set_platform(args.platform)        # 'cpu' | 'gpu' | 'tpu' | None
    pf.use_compile_cache()                # persistent compile cache
    pf.request_mesh_devices(args.mesh)    # CPU: emulated host devices
    ...first jax device use happens after...

The GPU flags mirror the latency-oriented serving profile: the
latency-hiding scheduler overlaps the ingest ring's ``device_put``
uploads with in-flight scatter+read dispatches (the whole point of the
device-resident ingress path in ``serve.stream``), and async collectives
keep the sharded slot-pool plan's (collective-free) hot path from
serializing against any host-driven transfer.
"""
from __future__ import annotations

import os
import pathlib
import re
from typing import Dict, Optional

import jax

__all__ = [
    "PLATFORMS", "GPU_XLA_FLAGS", "HOST_DEVICE_COUNT_FLAG",
    "merge_xla_flags", "set_platform", "enable_x64", "debug_nans",
    "set_host_device_count", "ensure_host_device_count",
    "requested_platform", "request_mesh_devices", "COMPILE_CACHE_DIR",
    "use_compile_cache", "describe",
]

#: the platforms ``set_platform`` accepts (None = let jax pick)
PLATFORMS = ("cpu", "gpu", "tpu")

#: XLA performance flags applied when the gpu platform is selected:
#: latency-hiding scheduling (overlap host->device ingest uploads with
#: compute), async collectives on their own high-priority stream, and
#: the triton gemm/softmax fusions the stage-1 heads benefit from
GPU_XLA_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_triton_softmax_fusion=true",
)

#: the emulated host-device-count flag (CPU multi-device testing)
HOST_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

#: the persistent compilation cache's directory when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed path inside the
#: checkout (the directory is part of the cache key, so a path that moved
#: between runs would never hit)
COMPILE_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[2]
                        / ".jax_cache")


def merge_xla_flags(new_flags, env: Optional[Dict[str, str]] = None) -> str:
    """Merge ``new_flags`` into ``XLA_FLAGS`` without duplicating or
    clobbering unrelated flags already present.

    A flag whose ``--name`` is already set keeps its existing value (the
    user's explicit environment wins over our defaults); everything else
    appends.  Returns the merged string and writes it back to ``env``
    (default ``os.environ``) — pure when passed a plain dict, which is
    how the tests cover it without touching the process environment.
    """
    env = os.environ if env is None else env
    current = env.get("XLA_FLAGS", "")
    present = {
        m.group(1) for m in re.finditer(r"(--[\w-]+)(?:=\S*)?", current)
    }
    parts = [current] if current else []
    for flag in new_flags:
        name = flag.split("=", 1)[0]
        if name not in present:
            parts.append(flag)
            present.add(name)
    merged = " ".join(parts)
    if merged:
        env["XLA_FLAGS"] = merged
    return merged


def set_platform(platform: Optional[str],
                 env: Optional[Dict[str, str]] = None) -> None:
    """Select the jax platform for this process (``None`` = leave jax's
    own auto-detection alone).

    Must run before the backend initializes.  Selecting ``"gpu"`` also
    merges the :data:`GPU_XLA_FLAGS` serving profile into ``XLA_FLAGS``
    (existing explicit settings win; see :func:`merge_xla_flags`).
    """
    if platform is None:
        return
    if platform not in PLATFORMS:
        raise ValueError(
            f"unknown platform {platform!r}; expected one of {PLATFORMS} "
            "or None"
        )
    if platform == "gpu":
        merge_xla_flags(GPU_XLA_FLAGS, env)
    jax.config.update("jax_platform_name", platform)


def enable_x64(use_x64: bool = True) -> None:
    """Toggle 64-bit jax arithmetic.

    The serving stack is float32 end to end (the SAE stores float32
    offsets; see ``serve.stream``'s epoch rebasing for how long-horizon
    timestamps stay precise anyway), so this is off by default — it
    exists for offline analysis runs that want float64 references.
    """
    jax.config.update("jax_enable_x64", bool(use_x64))


def debug_nans(flag: bool = True) -> None:
    """Toggle jax NaN debugging (slow; never in the serving hot path)."""
    jax.config.update("jax_debug_nans", bool(flag))


def set_host_device_count(n: int, env: Optional[Dict[str, str]] = None) -> str:
    """Request ``n`` emulated host-platform (CPU) devices via
    ``XLA_FLAGS`` — the multi-device-on-CPU testing story.

    Only effective before the backend initializes; this writes the flag
    (raising an existing smaller count) and returns the merged
    ``XLA_FLAGS``.  Use :func:`ensure_host_device_count` to also verify
    the backend actually honors it.
    """
    env = os.environ if env is None else env
    flags = env.get("XLA_FLAGS", "")
    present = re.search(rf"{HOST_DEVICE_COUNT_FLAG}=(\d+)", flags)
    if present is None:
        merged = f"{flags} {HOST_DEVICE_COUNT_FLAG}={n}".strip()
    elif int(present.group(1)) < n:
        merged = flags.replace(present.group(0),
                               f"{HOST_DEVICE_COUNT_FLAG}={n}")
    else:
        merged = flags
    env["XLA_FLAGS"] = merged
    return merged


def ensure_host_device_count(n: int) -> None:
    """:func:`set_host_device_count` + verify the backend honors it.

    Raises when the jax backend already initialized with fewer devices —
    the caller touched jax device state too early for the flag to take.
    """
    set_host_device_count(n)
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"asked for {n} host devices but the jax backend already "
            f"initialized with {len(jax.devices())}; set "
            f"XLA_FLAGS={HOST_DEVICE_COUNT_FLAG}={n} before any jax "
            "device use"
        )


def requested_platform() -> Optional[str]:
    """The platform this process asked jax for, read without
    initializing the backend: the first entry of ``jax_platforms``
    (``JAX_PLATFORMS``), else ``jax_platform_name`` (``set_platform``);
    ``None`` when jax is left to pick."""
    platforms = jax.config.jax_platforms or ""
    first = platforms.split(",")[0].strip()
    return first or jax.config.read("jax_platform_name") or None


def request_mesh_devices(n: int) -> None:
    """Prepare ``n`` devices for an ``n``-way mesh; call before the first
    jax device use.

    Host-device emulation is the CPU platform's way to have ``n``
    devices, so the flag is written only when the process asked for the
    CPU or left the choice to jax (the flag shapes the CPU client alone,
    and is inert when jax then picks an accelerator).  On a TPU the mesh
    takes the real chips; ``launch.mesh.make_host_mesh`` fails, naming
    chips, when the host has fewer than ``n``.
    """
    if requested_platform() in (None, "cpu"):
        set_host_device_count(n)


def use_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here.  Otherwise the cache goes to the fixed
    :data:`COMPILE_CACHE_DIR` (never a temporary, PID- or time-based
    path).  Call from an entry point's ``main``, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def describe() -> Dict[str, object]:
    """One-line process platform summary for launch banners and CI logs.

    Touches jax device state (initializes the backend) — call it *after*
    the set_* functions above.
    """
    from repro.kernels import ops

    return {
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "x64": bool(jax.config.read("jax_enable_x64")),
        "kernel_backend": ops.resolve_backend(None),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }
