"""The chip smoke script, rehearsed on CPU at a tiny size: every phase runs
its checks (oracle tracker, host f32 forward, service replay) in Pallas
interpret mode, the four-lane phase runs on forced host devices, and the
script refuses to report a result without a TPU or outside a checkout."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(table_size=64, batch_size=32, max_ready=8, scan_len=2,
                        flows=12, steps=16, cold_size=256, cold_flows=96,
                        cold_steps=8, buckets=(8, 16, 32), clients=3,
                        requests=3)


def test_phases_pass_at_tiny_size():
    assert chip_smoke.run_phases(TINY, seed=0) == []


def _run(args, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=600)


def _json_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def test_four_lane_phase_on_forced_host_devices():
    code = textwrap.dedent(f"""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{os.path.join(ROOT, "src")!r}, {os.path.join(ROOT, "tests")!r},
                    {ROOT!r}]
    import chip_smoke
    from test_chip_smoke import TINY
    chip_smoke.run_four_chips(TINY, seed=0)
    print("OK four lanes")
    """)
    out = _run(["-c", code], ROOT)
    assert out.returncode == 0, out.stdout + out.stderr[-4000:]
    assert "backend=shard_map" in out.stdout and "OK four lanes" in out.stdout


def test_refuses_without_tpu():
    out = _run([os.path.join(ROOT, "chip_smoke.py")], ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert not _json_lines(out.stdout)


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert not _json_lines(out.stdout)


def test_compile_cache_follows_the_environment(tmp_path):
    code = textwrap.dedent(f"""
    import os, sys
    sys.path.insert(0, {os.path.join(ROOT, "src")!r})
    import jax, jax.numpy as jnp
    from repro.runtime import platform
    path = platform.enable_compile_cache()
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        assert path == os.environ["JAX_COMPILATION_CACHE_DIR"], path
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
        assert os.listdir(path), "nothing cached"
    else:
        assert path == str(platform.CHECKOUT_CACHE_DIR), path
    assert jax.config.jax_compilation_cache_dir == path
    print("OK", path)
    """)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    cache = tmp_path / "cache"
    for extra in ({"JAX_COMPILATION_CACHE_DIR": str(cache)}, {}):
        out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                             env=dict(env, JAX_PLATFORMS="cpu", **extra),
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr[-4000:]
    assert os.listdir(cache)
