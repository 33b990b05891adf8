"""Process-level platform configuration (``repro.platform``).

Everything here runs against plain dict environments — never the real
``os.environ`` or the live jax backend state — because the whole point
of the module is that these knobs only matter *before* backend
initialization, which the test process has long passed.
"""
import os

import jax
import pytest

from repro import platform as pf


# ---------------------------------------------------------------------------
# merge_xla_flags: non-clobbering, deduplicating, pure over a dict env
# ---------------------------------------------------------------------------

def test_merge_xla_flags_appends_into_empty_env():
    env = {}
    merged = pf.merge_xla_flags(("--a=1", "--b"), env)
    assert merged == "--a=1 --b"
    assert env == {"XLA_FLAGS": "--a=1 --b"}


def test_merge_xla_flags_never_clobbers_existing_values():
    """A flag the user already set keeps the user's value; only the
    genuinely new flags append."""
    env = {"XLA_FLAGS": "--a=user --other_thing=7"}
    merged = pf.merge_xla_flags(("--a=ours", "--b=2"), env)
    assert merged == "--a=user --other_thing=7 --b=2"
    assert env["XLA_FLAGS"] == merged


def test_merge_xla_flags_dedupes_within_new_flags():
    env = {}
    merged = pf.merge_xla_flags(("--a=1", "--a=2"), env)
    assert merged == "--a=1"


def test_merge_xla_flags_is_idempotent():
    env = {}
    pf.merge_xla_flags(pf.GPU_XLA_FLAGS, env)
    once = env["XLA_FLAGS"]
    pf.merge_xla_flags(pf.GPU_XLA_FLAGS, env)
    assert env["XLA_FLAGS"] == once


def test_merge_xla_flags_pure_when_given_a_dict():
    import os

    before = os.environ.get("XLA_FLAGS")
    pf.merge_xla_flags(("--only_in_the_dict=1",), {})
    assert os.environ.get("XLA_FLAGS") == before


# ---------------------------------------------------------------------------
# set_platform / set_host_device_count
# ---------------------------------------------------------------------------

def test_set_platform_rejects_unknown():
    with pytest.raises(ValueError, match="unknown platform"):
        pf.set_platform("quantum")


def test_set_platform_none_is_a_noop():
    pf.set_platform(None)   # must not raise, must not touch config


def test_set_platform_gpu_merges_serving_flags(monkeypatch):
    """Selecting gpu installs the latency-oriented serving profile into
    XLA_FLAGS (without clobbering user overrides) and sets the jax
    platform name."""
    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v))
    env = {"XLA_FLAGS": "--xla_gpu_triton_gemm_any=False"}
    pf.set_platform("gpu", env)
    assert seen == {"jax_platform_name": "gpu"}
    flags = env["XLA_FLAGS"].split()
    assert "--xla_gpu_triton_gemm_any=False" in flags     # user wins
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in flags
    assert not any(f == "--xla_gpu_triton_gemm_any=True" for f in flags)


def test_set_host_device_count_writes_and_raises_counts():
    env = {}
    merged = pf.set_host_device_count(4, env)
    assert merged == f"{pf.HOST_DEVICE_COUNT_FLAG}=4"
    # a larger request raises the count in place...
    pf.set_host_device_count(8, env)
    assert env["XLA_FLAGS"] == f"{pf.HOST_DEVICE_COUNT_FLAG}=8"
    # ...a smaller one never lowers it (an emulated 8-device process
    # satisfies any <=8 mesh request)
    pf.set_host_device_count(2, env)
    assert env["XLA_FLAGS"] == f"{pf.HOST_DEVICE_COUNT_FLAG}=8"


def test_set_host_device_count_preserves_other_flags():
    env = {"XLA_FLAGS": "--a=1"}
    pf.set_host_device_count(4, env)
    assert env["XLA_FLAGS"] == f"--a=1 {pf.HOST_DEVICE_COUNT_FLAG}=4"


def test_ensure_host_device_count_raises_after_backend_init():
    """The test process's backend is long initialized with one CPU
    device, so asking for more must fail loudly (the flag can no longer
    take effect) — and the error says what to set."""
    n = len(jax.devices()) + 7
    with pytest.raises(RuntimeError, match=pf.HOST_DEVICE_COUNT_FLAG):
        pf.ensure_host_device_count(n)
    # a satisfiable request is fine after init
    pf.ensure_host_device_count(1)


def test_request_mesh_devices_emulates_only_for_the_cpu(monkeypatch):
    """Host-device emulation is written for a CPU (or undecided)
    platform only; a process that asked for the TPU takes its chips."""
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setattr(pf, "requested_platform", lambda: "tpu")
    pf.request_mesh_devices(4)
    assert os.environ["XLA_FLAGS"] == ""
    for asked in ("cpu", None):
        monkeypatch.setattr(pf, "requested_platform", lambda: asked)
        pf.request_mesh_devices(4)
        assert os.environ["XLA_FLAGS"] == f"{pf.HOST_DEVICE_COUNT_FLAG}=4"


@pytest.mark.parametrize("platforms,name,want", [
    ("tpu,cpu", "", "tpu"), ("cpu", "", "cpu"), ("", "gpu", "gpu"),
    ("", "", None),
])
def test_requested_platform_reads_config_without_backend(
        monkeypatch, platforms, name, want):
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        property(lambda self: platforms))
    monkeypatch.setattr(jax.config, "read", lambda key: name)
    assert pf.requested_platform() == want


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

def test_use_compile_cache_leaves_an_env_dir_to_jax(monkeypatch):
    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert pf.use_compile_cache() == "/elsewhere/cache"
    assert seen == {}                      # nothing set in code


def test_use_compile_cache_defaults_to_the_fixed_repo_path(monkeypatch):
    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.__setitem__(k, v))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = pf.use_compile_cache()
    assert path == pf.COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert seen == {"jax_compilation_cache_dir": path}
    assert pf.use_compile_cache() == path   # same path every call


def test_describe_reports_live_process_state():
    d = pf.describe()
    assert d["backend"] == jax.default_backend()
    assert d["n_devices"] == jax.device_count() >= 1
    assert d["x64"] is False               # serving stack is float32
    from repro.kernels import ops

    assert d["kernel_backend"] == ops.resolve_backend(None)
