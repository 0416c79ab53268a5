"""Ahead-of-time compiles for a described (not attached) TPU v5e chip.

The TPU compiler is installed on CPU hosts too: a program lowered against a
described topology is compiled exactly as the chip's compiler would compile
it, so what Mosaic or XLA refuses shows up here, at real sizes, without a
chip.  Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture — never while a module is
imported — and every test here skips when it cannot be described.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.distributed import sharding as shd
from repro.kernels.arype_matmul import arype_matmul
from repro.kernels.flow_features.ops import META_WIDTH, fold_features
from repro.kernels.vpe_smallmm import vpe_matmul
from repro.models import paper_models
from repro.runtime import RuntimeConfig
from repro.serving import (OctopusPipeline, PipelineConfig,
                           ShardedOctopusPipeline, sharded)

TABLE = 8192  # the paper's flow table
BATCH = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without that chip; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The ``lanes`` mesh over the four described chips."""
    return Mesh(np.asarray(topo.devices), (shd.LANES_AXIS,))


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _sds(sharding, a.shape, a.dtype), tree)


def test_arype_matmul_compiles(one_chip):
    f32 = jnp.float32
    compiled = arype_matmul.lower(_sds(one_chip, (1024, 256), f32),
                                  _sds(one_chip, (256, 128), f32),
                                  interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vpe_matmul_compiles_at_cnn_first_layer(one_chip):
    f32 = jnp.float32
    compiled = vpe_matmul.lower(_sds(one_chip, (20000, 3), f32),
                                _sds(one_chip, (3, 32), f32),
                                interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flow_features_fold_compiles(one_chip):
    def fold(program, slots, meta, feats, keep):
        return fold_features(program, slots, meta, feats, keep=keep,
                             interpret=False)

    compiled = jax.jit(fold).lower(
        _sds(one_chip, (16, 3)), _sds(one_chip, (BATCH,)),
        _sds(one_chip, (BATCH, META_WIDTH)), _sds(one_chip, (TABLE, 16)),
        _sds(one_chip, (BATCH,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_pallas,cold_size",
                         [(False, 0), (True, 0), (False, 131072)])
def test_pipeline_step_compiles(one_chip, use_pallas, cold_size):
    """The fused step (tracker merge, drain, both engines, decide) at the
    paper's table depth, hot-only and with a cold tier.  Plain XLA without
    Pallas; with it, the tracker fold and both engine kernels are Mosaic
    custom calls."""
    cfg = PipelineConfig(batch_size=BATCH, max_ready=64, flow_model="cnn",
                         table_size=TABLE, cold_size=cold_size)
    pipe = OctopusPipeline(
        paper_models.init_paper_model("mlp", jax.random.PRNGKey(0)),
        paper_models.init_paper_model("cnn", jax.random.PRNGKey(1)), cfg,
        config=RuntimeConfig(use_pallas=use_pallas, interpret=False))
    compiled = jax.jit(pipe._step).lower(
        _abstract(pipe.state, one_chip),
        _abstract(pipe._zero_batch(), one_chip)).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == use_pallas
    if cold_size:
        # the cold tier's promote and spill walks are while loops that carry
        # only (C,) bookkeeping leaves; every wide cold leaf (features,
        # series, sizes, payload) is scattered outside them
        whiles = [line for line in text.splitlines()
                  if re.search(r"= \(.*\) while\(", line)]
        assert sum(f"s32[{cold_size}]" in line for line in whiles) >= 2
        assert not any(f"s32[{cold_size}," in line for line in whiles)


COLLECTIVE = re.compile(r"= .*\b(all-gather|all-reduce|all-to-all|collective-permute"
                        r"|reduce-scatter)(-start)?\(")


def test_four_lane_step_compiles(four_chips, monkeypatch):
    """The served four-lane step of ``ids-cnn-x4`` (bucket 1024, a hot table
    and a cold tier per lane, one lane per chip).  Each lane's banks stay on
    its chip: no collective moves a table or cold-tier leaf.  The ones the
    merge needs (verdicts back to batch order, the counters' sums) are
    named by its ``lanes.merge`` scope."""
    bucket, cold = 1024, 131072
    fresh = ShardedOctopusPipeline._fresh_state

    def abstract_state(self):  # shapes only: nothing lives on a described chip
        mesh, self.mesh = self.mesh, None
        try:
            return jax.eval_shape(lambda: fresh(self))
        finally:
            self.mesh = mesh

    monkeypatch.setattr(sharded, "make_lanes_mesh", lambda n: four_chips)
    monkeypatch.setattr(ShardedOctopusPipeline, "_fresh_state", abstract_state)
    cfg = PipelineConfig(batch_size=bucket, max_ready=256, flow_model="cnn",
                         table_size=TABLE, cold_size=cold)
    pipe = ShardedOctopusPipeline(
        paper_models.init_paper_model("mlp", jax.random.PRNGKey(0)),
        paper_models.init_paper_model("cnn", jax.random.PRNGKey(1)), cfg,
        num_shards=4, backend="shard_map",
        config=RuntimeConfig(use_pallas=False, interpret=False))

    def lanes(tree):
        return jax.tree_util.tree_map(lambda a: _sds(
            NamedSharding(four_chips, shd.lanes_spec(a.ndim - 1)), a.shape,
            a.dtype), tree)

    zb = jax.eval_shape(lambda: pipe._zero_parts(bucket))
    text = pipe._masked_fn.lower(lanes(pipe.state), lanes(zb.shards),
                                 lanes(zb.keep), lanes(zb.src)).compile().as_text()
    collectives = [line for line in text.splitlines() if COLLECTIVE.search(line)]
    assert collectives
    wide = re.compile(rf"[\[,]({TABLE}|{cold})[\],]")
    for line in collectives:
        assert not wide.search(line.split("metadata=", 1)[0]), line
        assert "/lanes.merge/" in line, line


@pytest.mark.parametrize("lanes", [1, 4])
def test_host_pack_compiles_as_one_array(one_chip, four_chips, lanes):
    """The host pack of the served step's outputs (``ids-cnn`` at bucket
    256 with the echoed hashes; ``ids-cnn-x4`` at 1024, its drained rows
    split over the lanes' chips) compiles to ONE array that every chip
    holds whole, so its read-back is a single copy from one chip."""
    from repro.serving.pipeline import COUNTERS, _flatten

    bucket, rows = (256, 64) if lanes == 1 else (1024, 256)
    whole = one_chip if lanes == 1 else NamedSharding(four_chips, PartitionSpec())
    split = one_chip if lanes == 1 else NamedSharding(four_chips, shd.lanes_spec())
    leaves = [_sds(whole, (bucket,)), _sds(split, (rows,), jnp.bool_),
              *(_sds(split, (rows,)) for _ in range(3)),
              *(_sds(whole, ()) for _ in COUNTERS)]
    if lanes == 1:
        leaves.append(_sds(one_chip, (bucket,)))  # the echoed tuple hashes
    compiled = _flatten.lower(tuple(leaves)).compile()
    out = compiled.output_shardings
    assert out.is_fully_replicated and len(out.device_set) == lanes
    assert compiled.out_info.shape == (bucket * (2 if lanes == 1 else 1)
                                       + 4 * rows + len(COUNTERS),)
