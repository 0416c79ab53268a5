"""GPipe-style pipeline parallelism over the ``pod`` axis (shard_map +
collective_permute).

At 1000+ node scale, cross-pod ICI/DCN links are much slower than intra-pod
links, so the pod axis prefers pipeline transfers (point-to-point, one
activation tensor per microbatch) over data-parallel all-reduces of full
gradients.  This module implements the schedule:

  * the layer stack is split into ``num_stages`` contiguous groups,
  * microbatches stream through stages with ``collective_permute`` handoffs,
  * the standard GPipe bubble: (stages-1) warmup + (stages-1) drain slots of
    the (microbatches + stages - 1)-slot schedule.

The implementation is deliberately stage-generic: ``stage_fn(stage_params,
x, stage_index)`` is user code (usually a superblock scan slice).  A CPU
integration test validates numerical equality with the unpipelined model on
an 8-device host mesh.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    stage_params: Any,  # pytree with leading [num_stages] dim, sharded over axis
    x_microbatches: jax.Array,  # (num_micro, mb, ...) input activations
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Runs the GPipe forward schedule inside shard_map over ``axis``.

    Every device along ``axis`` holds one stage's params (leading dim sharded).
    Microbatch i enters stage 0 at slot i; stage s processes microbatch
    (slot - s); outputs stream off the last stage.  Returns (num_micro, mb, ...)
    activations after all stages.
    """
    num_stages = mesh.shape[axis]
    num_micro = x_microbatches.shape[0]
    total_slots = num_micro + num_stages - 1

    def body(params_local, xs_local):
        # params_local: stage params with leading dim 1 (this device's stage)
        # xs_local: full microbatch stream (replicated along `axis`)
        stage_idx = lax.axis_index(axis)
        my_params = jax.tree.map(lambda p: p[0], params_local)

        def slot_step(carry, t):
            state, outputs = carry  # state: (mb, ...) current activation
            # stage 0 ingests microbatch t; others take the permuted input
            incoming = jnp.where(
                t < num_micro,
                xs_local[jnp.minimum(t, num_micro - 1)],
                jnp.zeros_like(xs_local[0]),
            )
            inp = jnp.where(stage_idx == 0, incoming, state)
            out = stage_fn(my_params, inp, stage_idx)
            # hand off to the next stage (ring permute; last->first is ignored)
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            state_next = lax.ppermute(out, axis, perm)
            # the LAST stage emits microbatch (t - (num_stages - 1)) at slot t
            emit_idx = t - (num_stages - 1)
            is_emit = (stage_idx == num_stages - 1) & (emit_idx >= 0)
            outputs = lax.cond(
                is_emit,
                lambda o: o.at[jnp.maximum(emit_idx, 0)].set(out),
                lambda o: o,
                outputs,
            )
            return (state_next, outputs), None

        out0 = jnp.zeros_like(xs_local)
        state0 = jnp.zeros_like(xs_local[0])
        (_, outputs), _ = lax.scan(slot_step, (state0, out0), jnp.arange(total_slots))
        # only the last stage holds real outputs; broadcast them along the axis
        outputs = lax.psum(
            jnp.where(stage_idx == num_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis,
        )
        return outputs

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),  # microbatch stream replicated along the pipeline axis
    )
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_microbatches)


def split_stages(stacked_params: Any, num_stages: int) -> Any:
    """Reshape a [num_layers, ...] stacked param tree into
    [num_stages, layers_per_stage, ...]."""

    def one(p):
        n = p.shape[0]
        assert n % num_stages == 0, (n, num_stages)
        return p.reshape(num_stages, n // num_stages, *p.shape[1:])

    return jax.tree.map(one, stacked_params)
