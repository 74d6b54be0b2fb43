"""The rules chip bring-up rests on: the platform name goes to JAX as given
(no alias, no fallback), one placeable compile cache, a cluster router that
stays off the device, and a smoke script that cannot pass without a chip."""

import json
import os
import subprocess
import sys

import jax
import pytest

from distkeras_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_force_platform_tpu_means_tpu_and_nothing_else(monkeypatch):
    """No alias and no ``,cpu`` tail: on a host without a chip 'tpu' must
    fail at backend init, not run quietly on the CPU."""
    seen = {}
    monkeypatch.setattr(platform, "jax_backends_live", lambda: False)
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: seen.__setitem__(key, value))
    platform.force_platform("tpu")
    assert seen == {"jax_platforms": "tpu"}
    # default / None leave the choice to JAX (and to JAX_PLATFORMS)
    seen.clear()
    platform.force_platform(None)
    platform.force_platform("default")
    assert seen == {}


def test_compile_cache_env_wins_untouched(monkeypatch):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; with it set the helper
    must not call jax.config.update at all."""
    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert platform.use_compile_cache() == "/some/dir"
    assert calls == []


def test_compile_cache_defaults_inside_the_checkout(monkeypatch):
    calls = {}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.__setitem__(key, value))
    path = platform.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == {"jax_compilation_cache_dir": path}


def test_cluster_parent_validates_mesh_without_touching_a_device(monkeypatch):
    """`run.py cluster --mesh-shape` must not call jax.devices() in the
    router process: a parent that holds the chip starves every replica
    child. The spec's syntax is checked there; the device count is the
    children's business."""
    import distkeras_tpu.serving.cluster as cluster_mod
    from distkeras_tpu.run import serve_main

    def no_devices(*a, **k):
        raise AssertionError("the cluster parent initialized a JAX backend")

    class Reached(Exception):
        pass

    def stop_here(*a, **k):
        raise Reached

    monkeypatch.setattr(jax, "devices", no_devices)
    monkeypatch.setattr(jax, "local_devices", no_devices)
    monkeypatch.setattr(cluster_mod, "ServingCluster", stop_here)
    # A shape no host here could satisfy still passes the parent...
    with pytest.raises(Reached):
        serve_main(["--replicas", "2", "--mesh-shape", "tp=64",
                    "--port", "0"])
    with pytest.raises(Reached):
        serve_main(["--replicas", "2", "--mesh", "--port", "0"])
    # ...while a spec that does not parse fails there, typed.
    with pytest.raises(SystemExit) as e:
        serve_main(["--replicas", "2", "--mesh-shape", "tp=banana",
                    "--port", "0"])
    assert "--mesh-shape" in str(e.value)


def test_bench_refuses_to_measure_off_chip():
    """bench.py has no ladder to walk down: without a tpu (and without
    BENCH_PLATFORM=cpu asked for by name) it prints no result and exits
    non-zero."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_PLATFORM"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no fallback" in out.stderr


def test_chip_smoke_rehearsal_on_cpu_is_never_a_pass():
    """`chip_smoke.py --rehearse` walks every phase at toy size on the CPU
    and must still end ok=false, non-zero: no path reports success without
    a tpu device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the smoke's children size their own world
    out = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse"],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"
    # The rehearsal itself went through: every phase ran and passed its
    # own checks; only the platform rule fails it.
    summary = [ln for ln in lines if ln.startswith("[summary]")]
    for phase in ("serve", "train", "async"):
        assert any(f"{phase}: PASS" in ln for ln in summary), out.stdout[-3000:]
