"""Process-level runtime/platform configuration — the ONE place that owns
the knobs which must be set before jax initializes its backend.

Three kinds of knob live here, in order of how early they must fire:

  * **XLA_FLAGS** (``ensure_host_devices``, ``apply_gpu_autotune``) — env
    edits that only take effect if they precede the FIRST jax backend
    initialization.  The emulated-device knob
    (``--xla_force_host_platform_device_count=N``) is how CI exercises a
    REAL 8-device mesh on a CPU host: every sharding, collective, and
    donation path runs exactly as on hardware, just slower.  Editing is
    idempotent (re-applying the same count is a no-op) and guarded — a
    different count after the backend already locked raises instead of
    silently doing nothing.
  * **jax.config toggles** (``set_platform``, ``enable_x64``,
    ``set_debug_nan``) — applied through ``jax.config.update``; safe at
    any time before the relevant behavior is traced.
  * **the persistent compile cache** (``enable_compile_cache``) — where
    compiled programs are kept between processes; entry points call it
    before their first compile.
  * **introspection** (``describe``) — the resolved platform / device kind
    / device count / mesh-relevant process info, recorded by every
    benchmark writer so a ``BENCH_*.json`` is interpretable across
    machines (see ``benchmarks/run.py`` ``bench_meta``).

This module IMPORTS NO JAX AT MODULE SCOPE — importing it can never lock
the device count.  ``launch/roofline.py`` and ``launch/dryrun.py`` call
``ensure_host_devices(512)`` as their first statement instead of the
hand-rolled ``os.environ["XLA_FLAGS"] = ...`` strings they used to carry.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import re
import sys
from typing import Any, Dict, Optional

log = logging.getLogger(__name__)

_HOST_DEV_FLAG = "--xla_force_host_platform_device_count"
_HOST_DEV_RE = re.compile(re.escape(_HOST_DEV_FLAG) + r"=(\d+)")

# the bayespec-style GPU autotune set: triton fusions + async collectives
# + latency-hiding scheduling.  Harmless off-GPU (XLA ignores unknown
# backend flags for other platforms); applied only on request.
GPU_AUTOTUNE_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true "
    "--xla_gpu_triton_gemm_any=True "
    "--xla_gpu_enable_async_collectives=true "
    "--xla_gpu_enable_latency_hiding_scheduler=true "
    "--xla_gpu_enable_highest_priority_async_stream=true"
)


def backend_initialized() -> bool:
    """Whether a jax backend has already been created in this process —
    the point after which XLA_FLAGS edits are dead letters."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge  # noqa: PLC0415

    return xla_bridge.backends_are_initialized()


def requested_host_devices() -> Optional[int]:
    """The emulated-device count currently requested via XLA_FLAGS
    (None when the flag is absent)."""
    m = _HOST_DEV_RE.search(os.environ.get("XLA_FLAGS", ""))
    return int(m.group(1)) if m else None


def ensure_host_devices(n: int) -> int:
    """Idempotently request ``n`` emulated host-platform devices.

    MUST run before the first jax import in the process (the device count
    locks on first backend init).  Re-applying the already-requested count
    is a no-op — safe from module top-levels that may import each other.
    A DIFFERENT count is honored while the backend is uninitialized
    (the flag is rewritten in place) and raises once it is locked:
    silently keeping the stale count is how "works at 1x1 only" bugs
    hide.  Returns the requested count.
    """
    n = int(n)
    if n <= 0:
        raise ValueError(f"ensure_host_devices: need n >= 1, got {n}")
    current = requested_host_devices()
    if current == n:
        return n
    if backend_initialized():
        raise RuntimeError(
            f"ensure_host_devices({n}): jax backend already initialized "
            f"(current request: {current}); emulated device count can only "
            "be set before the first jax import — call this from the "
            "module top, like launch/roofline.py does")
    flags = os.environ.get("XLA_FLAGS", "")
    if current is not None:
        flags = _HOST_DEV_RE.sub(f"{_HOST_DEV_FLAG}={n}", flags)
    else:
        flags = f"{_HOST_DEV_FLAG}={n} {flags}".strip()
    os.environ["XLA_FLAGS"] = flags
    return n


def apply_gpu_autotune() -> None:
    """Append the GPU autotune XLA flag set (idempotent: flags already
    present in XLA_FLAGS are not duplicated)."""
    flags = os.environ.get("XLA_FLAGS", "")
    missing = [f for f in GPU_AUTOTUNE_FLAGS.split()
               if f.split("=")[0] not in flags]
    if not missing:
        return
    if backend_initialized():
        log.warning("apply_gpu_autotune: jax backend already initialized — "
                    "%d flag(s) will not take effect", len(missing))
    os.environ["XLA_FLAGS"] = (flags + " " + " ".join(missing)).strip()


def set_platform(platform: str) -> None:
    """Pin the jax platform ('cpu' | 'gpu' | 'tpu').  Uses the
    ``jax_platforms`` config when jax is already imported, the
    JAX_PLATFORMS env var otherwise (both are honored at backend init)."""
    platform = str(platform).lower()
    if platform not in ("cpu", "gpu", "tpu"):
        raise ValueError(f"set_platform: unknown platform {platform!r}")
    if backend_initialized():
        raise RuntimeError(
            f"set_platform({platform!r}): jax backend already initialized")
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", platform)
    else:
        os.environ["JAX_PLATFORMS"] = platform


def enable_x64(flag: bool = True) -> None:
    """Toggle double precision (``jax_enable_x64``)."""
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_enable_x64", bool(flag))
    else:
        os.environ["JAX_ENABLE_X64"] = "1" if flag else "0"


def set_debug_nan(flag: bool = True) -> None:
    """Toggle automatic NaN checking (``jax_debug_nans``) — tracing aid,
    never for production loops (it forces a sync per primitive)."""
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_debug_nans", bool(flag))
    else:
        os.environ["JAX_DEBUG_NANS"] = "1" if flag else "0"


# the checkout's own compile-cache directory (listed in .gitignore).  It is
# a fixed path so that every later process run from this checkout finds
# what earlier ones wrote: a temp, pid or time-based path would never hit
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads
    its directory from there and nothing else is set; otherwise the cache
    goes to the fixed in-checkout ``REPO_CACHE_DIR``.  Returns the
    directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax  # noqa: PLC0415

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


@dataclasses.dataclass(frozen=True)
class PlatformConfig:
    """Declarative bundle of the process-level knobs (``PALRunConfig``
    carries the same fields; ``configure`` applies them in the right
    order).  Zero values mean "leave alone"."""

    platform: str = ""          # '' | 'cpu' | 'gpu' | 'tpu'
    host_devices: int = 0       # >0: emulated host devices (CI meshes)
    x64: bool = False
    debug_nan: bool = False
    gpu_autotune: bool = False


def configure(cfg: Optional[PlatformConfig] = None, **kw: Any
              ) -> PlatformConfig:
    """Apply a ``PlatformConfig`` (or keyword overrides) in dependency
    order: XLA_FLAGS edits first (they need an uninitialized backend),
    then config toggles.  Returns the applied config."""
    cfg = dataclasses.replace(cfg or PlatformConfig(), **kw)
    if cfg.host_devices > 0:
        ensure_host_devices(cfg.host_devices)
    if cfg.gpu_autotune:
        apply_gpu_autotune()
    if cfg.platform:
        set_platform(cfg.platform)
    if cfg.x64:
        enable_x64(True)
    if cfg.debug_nan:
        set_debug_nan(True)
    return cfg


def configure_from_env(env: Optional[Dict[str, str]] = None
                       ) -> PlatformConfig:
    """Build + apply a ``PlatformConfig`` from ``REPRO_PLATFORM`` /
    ``REPRO_HOST_DEVICES`` / ``REPRO_X64`` / ``REPRO_GPU_AUTOTUNE`` —
    the launcher-script entry point (one env block instead of N ad-hoc
    ``os.environ`` edits)."""
    e = os.environ if env is None else env
    return configure(PlatformConfig(
        platform=e.get("REPRO_PLATFORM", ""),
        host_devices=int(e.get("REPRO_HOST_DEVICES", "0") or 0),
        x64=e.get("REPRO_X64", "") in ("1", "true"),
        gpu_autotune=e.get("REPRO_GPU_AUTOTUNE", "") in ("1", "true"),
    ))


def describe() -> Dict[str, Any]:
    """Resolved runtime facts for benchmark provenance (initializes the
    jax backend — never call from a module top that still wants to edit
    XLA_FLAGS): platform, device kind, device/process counts, and whether
    the devices are emulated host devices."""
    import jax  # noqa: PLC0415

    devs = jax.devices()
    return {
        "platform": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "?",
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "emulated_host_devices": requested_host_devices() or 0,
    }
