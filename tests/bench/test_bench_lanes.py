"""The four-lane cell through the harness on four virtual CPU devices: the
served path runs ``shard_map`` lanes, one lane's banks per device, and is
``correct``; with the lanes' answers left out of the merge it is not.

The device count is fixed before JAX starts, so the run is a subprocess,
and both runs share it (one compile of the bucket program)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys, time
    sys.path[:0] = [{root!r}, {src!r}, {here!r}]
    import jax
    import jax.numpy as jnp
    from bench import harness
    from test_bench_harness import tiny

    assert jax.local_device_count() == 4
    cell = tiny("ids-cnn-x4.churn.sat")
    seen = {{}}

    def grab(pipe, svc):
        seen["backend"] = pipe.backend
        # every leaf of the hot and cold banks: one lane on each device
        seen["banks"] = all(
            sorted(sh.device.id for sh in leaf.addressable_shards
                   if sh.data.shape[0] == 1) == [0, 1, 2, 3]
            for leaf in jax.tree_util.tree_leaves(pipe.state))

    def lane0_only(pipe, svc):
        grab(pipe, svc)
        merge = pipe._merge_out

        def merged(outs, src, *, batch=None):
            lanes = jnp.arange(src.shape[0])[:, None]
            d = outs.drained._replace(mask=outs.drained.mask & (lanes == 0))
            return merge(outs._replace(drained=d),
                         jnp.where(lanes == 0, src, src.shape[1]), batch=batch)
        pipe._merge_out = merged

    for name, patch in (("sound", grab), ("lane0_only", lane0_only)):
        r = harness.run_cell(cell, 2**31 + 11, 0.6, False,
                             t_start=time.perf_counter(), require_chip=False,
                             patch=patch)
        print(name, seen["backend"], seen["banks"], r["correct"],
              r["device"]["count"], flush=True)
""")


def test_four_lanes_on_four_devices_are_correct_and_need_their_merge():
    code = CODE.format(root=str(ROOT), src=str(ROOT / "src"),
                       here=str(Path(__file__).parent))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines()
                 if line.startswith(("sound ", "lane0_only ")))
    assert lines["sound"] == "shard_map True True 4", out.stdout
    assert lines["lane0_only"] == "shard_map True False 4", out.stdout
