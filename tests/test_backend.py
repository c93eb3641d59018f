"""Device discovery: chip specs keyed by device_kind, no silent CPU
fallback, the persistent compile cache's location, and the exit status
of the serving launcher."""

import types

import jax
import pytest

from repro.runtime import backend


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("platform,kind,spec", [
    ("tpu", "TPU v5 lite", backend.TPU_V5E),
    ("cpu", "cpu", backend.HOST_CPU),
])
def test_chip_spec_by_device_kind(platform, kind, spec):
    assert backend.chip_spec(_device(platform, kind)) is spec


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v4"),
    ("gpu", "NVIDIA H100"),
])
def test_unrecorded_device_kind_raises(platform, kind):
    with pytest.raises(backend.UnknownChipError, match=kind):
        backend.chip_spec(_device(platform, kind))


def test_cpu_spec_has_no_invented_peaks():
    assert backend.HOST_CPU.peak_bf16_flops is None
    assert backend.HOST_CPU.hbm_bandwidth is None


def test_interpret_mode_on_the_chosen_cpu():
    assert jax.default_backend() == "cpu"
    assert backend.pallas_interpret() is True


def test_cpu_standing_in_for_a_failed_tpu_raises(monkeypatch):
    """TPU chips attached + JAX on the CPU = the TPU backend failed to
    start: kernels must not drop to interpret mode, nor the registry to
    the CPU spec, without a word."""
    monkeypatch.setattr(backend, "host_tpu_chips", lambda: 4)
    with pytest.raises(backend.SilentCpuFallbackError):
        backend.pallas_interpret()
    with pytest.raises(backend.SilentCpuFallbackError):
        backend._BackendRegistry().load()


@pytest.mark.parametrize("platforms,chips", [("cpu", 0), ("cpu,cuda", 0)])
def test_jax_platforms_without_tpu_means_no_chips(monkeypatch, platforms,
                                                  chips):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert backend.host_tpu_chips() == chips


@pytest.mark.parametrize("nodes,chips", [
    ({"/dev/accel[0-9]*": ["/dev/accel0", "/dev/accel1"]}, 2),
    ({"/dev/vfio/[0-9]*": ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2",
                           "/dev/vfio/3"]}, 4),
    ({}, 0),
])
def test_tpu_chips_counted_from_device_nodes(monkeypatch, nodes, chips):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(backend.glob, "glob",
                        lambda pattern: nodes.get(pattern, []))
    assert backend.host_tpu_chips() == chips


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = backend.enable_compile_cache()
        assert path == str(backend.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        # a fixed path: the same on every call, in every process
        assert backend.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert backend.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the code sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_serve_mine_counts_request_errors():
    """``drive`` separates the service keeping its contract (suspended,
    dropped, shed) from requests that ended in an error — the latter
    make ``serve_mine`` exit nonzero."""
    from repro.launch.serve_mine import drive
    from repro.service import JobSuspended, RequestDropped

    class Handle:
        def __init__(self, exc):
            self.exc = exc

        def result(self, timeout=None):
            if self.exc is not None:
                raise self.exc
            return {}

    outcomes = iter([None, JobSuspended(3), RequestDropped("stopped"),
                     RuntimeError("Mosaic refused the kernel")])

    class Client:
        def submit(self, *args, **kwargs):
            return Handle(next(outcomes))

    workload = [("t", "kmeans", None, {})] * 4
    failures = drive(Client(), workload, rate=0.0, executor=None)
    assert failures == {"suspended": 1, "dropped": 1, "rejected": 0,
                        "errors": 1}
