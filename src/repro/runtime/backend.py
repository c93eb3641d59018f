"""Lazy backend discovery — the "wrapper library" layer.

The paper's wrapper library `dlopen`s the vendor OpenCL `.so` at runtime,
resolves symbols lazily immediately before first use, returns an error code
when called before load, and can be unloaded/reloaded.  The JAX analogue of
"do not link the accelerator at compile time" is: **never touch jax device
state at import time**.  This module keeps all device queries behind an
explicit :func:`load` / :func:`discover_backend` call guarded by the same
writer-preferred RW lock the paper uses for its load-state flag.

Why this matters here concretely: ``launch/dryrun.py`` must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
jax device query process-wide.  Any module that calls ``jax.devices()`` at
import time would lock the device count at 1 and silently break the
multi-pod dry-run — the exact class of bug the paper's lazy-loading design
exists to prevent (calling an OpenCL symbol before the library is loaded).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import pathlib
from typing import Any, List, Optional

from repro.runtime.locks import RWLock

# src/repro/runtime/backend.py -> the checkout root
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


class BackendNotLoadedError(RuntimeError):
    """Raised when a backend query is made before :func:`load`.

    Mirrors the paper: "If an OpenCL method of the wrapper library is called
    before the shared library has been loaded [...] an error is returned."
    """


class UnknownChipError(RuntimeError):
    """The devices JAX reports are not a chip this repository knows.

    Raised instead of guessing peaks or a memory budget for hardware whose
    numbers nobody recorded.
    """


class SilentCpuFallbackError(RuntimeError):
    """TPU chips are attached, yet JAX came up on the CPU.

    JAX skips a TPU backend that fails to start and carries on on the CPU;
    the Pallas kernels would then run in interpret mode without a word.
    """


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak-rate card for one accelerator chip (roofline constants).

    Peaks are None where no published number exists (the CPU backend).
    """

    name: str
    hbm_bytes: int          # device memory; the dispatch budget's basis
    peak_bf16_flops: Optional[float] = None  # FLOP/s
    hbm_bandwidth: Optional[float] = None    # byte/s
    ici_link_bandwidth: Optional[float] = None  # byte/s per link
    vmem_bytes: Optional[int] = None


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over 4 links.
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    hbm_bytes=16 * 1024**3,
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    vmem_bytes=128 * 1024**2,
)

# Keyed by ``device_kind`` exactly as JAX reports it on the chip.
CHIPS_BY_KIND = {"TPU v5 lite": TPU_V5E}

# The CPU backend (tests, interpret mode): host memory bounds a request,
# and it has no peaks to measure against.
HOST_CPU = ChipSpec(name="host_cpu", hbm_bytes=32 * 1024**3)


def chip_spec(device: Any) -> ChipSpec:
    """The spec of one JAX device; an unrecorded accelerator raises."""
    if device.platform == "cpu":
        return HOST_CPU
    try:
        return CHIPS_BY_KIND[device.device_kind]
    except KeyError:
        raise UnknownChipError(
            f"no ChipSpec for {device.platform} device_kind "
            f"{device.device_kind!r}; known kinds: {sorted(CHIPS_BY_KIND)}"
        ) from None


def host_tpu_chips() -> int:
    """TPU chips this process can open, counted without starting JAX.

    Counts the chips' device nodes: ``/dev/accel<N>``, or ``/dev/vfio/<N>``
    where the chips are bound to VFIO.  PCI is not counted: it lists every
    chip of the host, also those a container was not given.  0 where
    ``JAX_PLATFORMS`` holds JAX off the TPU: running on the CPU is then a
    choice, not a fallback.
    """
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return len(glob.glob("/dev/accel[0-9]*")
               or glob.glob("/dev/vfio/[0-9]*"))


def _refuse_silent_cpu(platform: str) -> None:
    if platform == "cpu" and host_tpu_chips() > 0:
        raise SilentCpuFallbackError(
            "this host has TPU chips but JAX runs on the CPU: the TPU "
            "backend failed to start (another process may hold the chip). "
            "Set JAX_PLATFORMS=cpu to run on the CPU on purpose.")


def pallas_interpret() -> bool:
    """Whether Pallas TPU kernels run in interpret mode: on the CPU only.

    On the TPU they compile to Mosaic.  Any other platform, or a CPU
    backend that stands in for a TPU that failed to start, raises.
    """
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform != "cpu":
        raise UnknownChipError(
            f"the Pallas kernels target the TPU; JAX runs on {platform!r}")
    _refuse_silent_cpu(platform)
    return True


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Called by entry points only, never at import time: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing
    is changed; otherwise the cache goes to ``<repo>/.jax_cache``.  The
    path is part of each entry's key, so it must not move between runs.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class Backend:
    """A loaded accelerator backend."""

    platform: str
    device_count: int
    devices: List[Any]
    chip: ChipSpec

    @property
    def is_tpu(self) -> bool:
        return self.platform == "tpu"


class _BackendRegistry:
    """Process-wide backend state behind the paper's RW lock discipline."""

    def __init__(self) -> None:
        self._lock = RWLock()
        self._backend: Optional[Backend] = None
        self._load_count = 0  # diagnostics: how many load/unload cycles

    def load(self) -> Backend:
        """Discover devices now (first jax device query happens here)."""
        with self._lock.write():
            if self._backend is None:
                import jax  # local import: keep module import side-effect free

                devices = jax.devices()
                platform = devices[0].platform
                _refuse_silent_cpu(platform)
                chip = chip_spec(devices[0])
                self._backend = Backend(
                    platform=platform,
                    device_count=len(devices),
                    devices=list(devices),
                    chip=chip,
                )
                self._load_count += 1
            return self._backend

    def unload(self) -> None:
        """Forget the backend (paper: library can be unloaded at runtime).

        jax itself keeps its client alive; this resets *our* view so tests can
        exercise the call-before-load error path.
        """
        with self._lock.write():
            self._backend = None

    def get(self) -> Backend:
        with self._lock.read():
            if self._backend is None:
                raise BackendNotLoadedError(
                    "backend not loaded; call repro.runtime.backend.load() first"
                )
            return self._backend

    @property
    def loaded(self) -> bool:
        with self._lock.read():
            return self._backend is not None

    @property
    def load_count(self) -> int:
        with self._lock.read():
            return self._load_count


_REGISTRY = _BackendRegistry()


def load() -> Backend:
    return _REGISTRY.load()


def unload() -> None:
    _REGISTRY.unload()


def get_backend() -> Backend:
    return _REGISTRY.get()


def discover_backend() -> Backend:
    """Load-if-needed and return the backend (the common entry point)."""
    return _REGISTRY.load()


def is_loaded() -> bool:
    return _REGISTRY.loaded
