"""launch/platform: the process-level runtime-config module.

The env-editing paths (XLA_FLAGS surgery) are tested in-process — they
are pure string/env manipulation.  The paths that need an UNinitialized
jax backend (flag rewrite actually changing the device count, module
import purity) run in subprocesses, which doubles as the tier-1 entry
that exercises a REAL 8-device emulated mesh end to end.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLAG = "--xla_force_host_platform_device_count"


def _run(code: str, **env):
    """Run a python snippet in a fresh interpreter with src/ importable."""
    full_env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full_env["PYTHONPATH"] = os.path.join(REPO, "src")
    full_env.update(env)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=full_env,
                          cwd=REPO, timeout=300)


def test_requested_host_devices_parses_flag(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", f"--xla_foo=1 {FLAG}=12")
    assert plat.requested_host_devices() == 12
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    assert plat.requested_host_devices() is None
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    assert plat.requested_host_devices() is None


def test_ensure_host_devices_same_count_is_noop(monkeypatch):
    # re-applying the already-requested count never needs the backend —
    # safe from module top-levels even after jax is live
    monkeypatch.setenv("XLA_FLAGS", f"{FLAG}=6 --xla_bar=2")
    assert plat.ensure_host_devices(6) == 6
    assert os.environ["XLA_FLAGS"] == f"{FLAG}=6 --xla_bar=2"


def test_ensure_host_devices_rejects_bad_count():
    with pytest.raises(ValueError):
        plat.ensure_host_devices(0)
    with pytest.raises(ValueError):
        plat.ensure_host_devices(-3)


def test_ensure_host_devices_raises_once_backend_locked(monkeypatch):
    jax.devices()                      # force backend init
    assert plat.backend_initialized()
    monkeypatch.setenv("XLA_FLAGS", f"{FLAG}=6")
    with pytest.raises(RuntimeError, match="already initialized"):
        plat.ensure_host_devices(3)


def test_set_platform_validates(monkeypatch):
    with pytest.raises(ValueError):
        plat.set_platform("quantum")
    jax.devices()
    with pytest.raises(RuntimeError, match="already initialized"):
        plat.set_platform("cpu")


def test_apply_gpu_autotune_idempotent(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_bar=2")
    plat.apply_gpu_autotune()
    after = os.environ["XLA_FLAGS"]
    assert "--xla_bar=2" in after
    for f in plat.GPU_AUTOTUNE_FLAGS.split():
        assert after.count(f.split("=")[0]) == 1
    plat.apply_gpu_autotune()          # second call: no duplicates
    assert os.environ["XLA_FLAGS"] == after


def test_configure_from_env_defaults():
    cfg = plat.configure_from_env({})
    assert cfg == plat.PlatformConfig()


def test_configure_applies_host_devices(monkeypatch):
    # count already requested -> configure is a no-op even when locked
    monkeypatch.setenv("XLA_FLAGS", f"{FLAG}=6")
    cfg = plat.configure_from_env({"REPRO_HOST_DEVICES": "6"})
    assert cfg.host_devices == 6
    assert plat.requested_host_devices() == 6


def test_describe_reports_runtime_facts():
    d = plat.describe()
    for key in ("platform", "device_kind", "device_count",
                "local_device_count", "process_index", "process_count",
                "emulated_host_devices"):
        assert key in d
    assert d["device_count"] == jax.device_count()
    assert d["process_count"] >= 1


def test_module_import_is_jax_free():
    # importing platform.py must NEVER initialize (or even import) jax —
    # that is the whole point of the module
    r = _run("""
        import sys
        import repro.launch.platform as plat
        assert "jax" not in sys.modules, "platform.py imported jax"
        print("PURE")
    """)
    assert r.returncode == 0, r.stderr
    assert "PURE" in r.stdout


def test_eight_device_mesh_end_to_end_subprocess():
    """Tier-1 entry for the emulated-device knob: a fresh process requests
    8 host devices (rewriting an existing flag), gets a REAL 8-device
    mesh, and fused scoring on it is bit-identical to unsharded."""
    r = _run(f"""
        import os
        os.environ["XLA_FLAGS"] = "{FLAG}=4 --xla_cpu_enable_fast_math=false"
        from repro.launch.platform import (ensure_host_devices,
                                           requested_host_devices)
        assert ensure_host_devices(8) == 8        # rewrite 4 -> 8
        assert requested_host_devices() == 8
        assert "--xla_cpu_enable_fast_math=false" in os.environ["XLA_FLAGS"]

        import jax
        import jax.numpy as jnp
        import numpy as np
        assert jax.device_count() == 8, jax.devices()
        ensure_host_devices(8)                    # locked same-count: ok
        try:
            ensure_host_devices(2)
            raise AssertionError("locked different count must raise")
        except RuntimeError:
            pass

        from repro.core.acquisition import FusedEngine
        from repro.core.committee import stack_members
        from repro.launch.mesh import make_scaleout_mesh

        D, H = 4, 8
        def init(seed):
            r = np.random.RandomState(seed)
            return {{"w1": jnp.asarray(r.randn(D, H).astype(np.float32)),
                     "w2": jnp.asarray(r.randn(H, D).astype(np.float32))}}
        cp = stack_members([init(i) for i in range(8)])
        apply_fn = lambda p, x: jnp.tanh(x @ p["w1"]) @ p["w2"]
        e0 = FusedEngine(apply_fn, cp, 0.5, impl="xla", mesh=None)
        e8 = FusedEngine(apply_fn, cp, 0.5, impl="xla",
                         mesh=make_scaleout_mesh(8, 1))
        x = list(np.random.RandomState(0).randn(16, D).astype(np.float32))
        r0, r8 = e0.score(x), e8.score(x)
        for f in ("mean", "scalar_std", "component_std", "mask"):
            assert np.array_equal(np.asarray(getattr(r0, f)),
                                  np.asarray(getattr(r8, f))), f
        print("MESH8_OK")
    """)
    assert r.returncode == 0, r.stderr
    assert "MESH8_OK" in r.stdout


def test_set_platform_pins_jax_platforms_after_import():
    r = _run("""
        import jax
        from repro.launch import platform as plat
        plat.set_platform("cpu")
        assert jax.config.jax_platforms == "cpu", jax.config.jax_platforms
        print("PINNED")
    """, JAX_PLATFORMS="")
    assert r.returncode == 0, r.stderr
    assert "PINNED" in r.stdout


def test_compile_cache_uses_env_dir_and_sets_no_other(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins: enable_compile_cache leaves jax's
    own reading of it alone, and compiled programs land there."""
    r = _run("""
        import os
        import jax, jax.numpy as jnp
        from repro.launch import platform as plat
        want = os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert plat.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(4)).block_until_ready()
        assert os.listdir(want), "no cache entry written"
        print("ENV_CACHE")
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr
    assert "ENV_CACHE" in r.stdout


def test_compile_cache_defaults_to_fixed_ignored_repo_dir():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax
            from repro.launch import platform as plat
            path = plat.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == path
            print(path)
        """)], capture_output=True, text=True, cwd=REPO, timeout=300,
        env=dict(env, PYTHONPATH=os.path.join(REPO, "src")))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
