"""Flow-feature ALU-cluster Pallas kernel (paper §3.1).

The FPGA feature extractor keeps an 8k-entry flow-state table; for each packet
a 16-lane ALU cluster folds the packet's *meta register* into the flow's
*history register* with per-lane micro-ops {nop, wr, add, sub, max, min, inc}.

TPU adaptation: the whole flow-state table (8192 x 16 int32) is copied into a
VMEM scratch once per call and written back once at the end (the HBM buffer
is aliased in place); packets stream through the grid in blocks; within a
block the kernel walks packets with ``fori_loop`` (updates to the same flow
must be ordered — this is the inherently sequential part the FPGA pipelines
at line rate).  The 16 feature lanes update vectorized, mirroring the 16
parallel ALUs.

What the TPU compiler (Mosaic) accepts shapes the kernel:

  * the per-packet slot index is a scalar, so the slot vector is scalar-
    prefetched into SMEM (a scalar read out of a VMEM vector is refused);
  * the meta-source selection (``meta[program[:, 1]]``) does not depend on
    the flow state, so it is gathered for the whole batch in XLA before the
    kernel, which then reads one (1, 16) operand row per packet;
  * the history-source selection (``hist[program[:, 2]]``) does depend on
    the state and is expressed as 16 lane-broadcast selects — Mosaic lowers
    no in-kernel gather and no int32 lane reduction;
  * the opcode dispatch is a chain of ``where`` (``jnp.select`` lowers
    through an argmax, which Mosaic supports only in float32).

Micro-op encoding per lane j (program row j = [opcode, meta_src, hist_src]):
  0 nop : out = hist[hist_src]
  1 wr  : out = meta[meta_src]
  2 add : out = hist[hist_src] + meta[meta_src]
  3 sub : out = hist[hist_src] - meta[meta_src]
  4 max : out = max(hist[hist_src], meta[meta_src])
  5 min : out = min(hist[hist_src], meta[meta_src])
  6 inc : out = hist[hist_src] + 1
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_LANES = 16


def apply_alu_program(program: jax.Array, meta: jax.Array, hist: jax.Array) -> jax.Array:
    """Vectorized 16-lane ALU cluster.  program: (16, 3) int32; meta: (M,) int32;
    hist: (16,) int32 -> new hist (16,) int32."""
    opcode = program[:, 0]
    a = jnp.take(meta, program[:, 1], axis=0)  # meta source per lane
    b = jnp.take(hist, program[:, 2], axis=0)  # history source per lane
    return jnp.select(
        [opcode == 0, opcode == 1, opcode == 2, opcode == 3, opcode == 4, opcode == 5, opcode == 6],
        [b, a, b + a, b - a, jnp.maximum(b, a), jnp.minimum(b, a), b + 1],
        default=b,
    ).astype(jnp.int32)


def _alu_lanes(opcode: jax.Array, hist_src: jax.Array, a: jax.Array,
               hist: jax.Array) -> jax.Array:
    """:func:`apply_alu_program` on (1, 16) rows, in the ops Mosaic lowers:
    ``a`` is the pre-gathered meta operand row, the history source is picked
    by lane-broadcast selects and the opcode by a ``where`` chain."""
    b = hist
    for k in range(N_LANES):
        b = jnp.where(hist_src == k, jnp.broadcast_to(hist[:, k:k + 1], hist.shape), b)
    out = b  # nop (and any unknown opcode)
    for code, val in ((1, a), (2, b + a), (3, b - a), (4, jnp.maximum(b, a)),
                      (5, jnp.minimum(b, a)), (6, b + 1)):
        out = jnp.where(opcode == code, val, out)
    return out


def _flow_kernel(slots_ref, ctrl_ref, a_ref, state_hbm, out_hbm, table, sem, *,
                 block: int, n_blocks: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _load():
        copy = pltpu.make_async_copy(state_hbm, table, sem)
        copy.start()
        copy.wait()

    opcode = ctrl_ref[0:1, :]
    hist_src = ctrl_ref[1:2, :]

    def body(i, carry):
        row = pl.ds(slots_ref[j * block + i], 1)
        table[row, :] = _alu_lanes(opcode, hist_src, a_ref[pl.ds(i, 1), :],
                                   table[row, :])
        return carry

    lax.fori_loop(0, block, body, 0)

    @pl.when(j == n_blocks - 1)
    def _store():
        copy = pltpu.make_async_copy(table, out_hbm, sem)
        copy.start()
        copy.wait()


def flow_update(
    program: jax.Array,  # (16, 3) int32
    slots: jax.Array,  # (P,) int32 flow-table row per packet
    meta: jax.Array,  # (P, M) int32 meta registers
    init_state: jax.Array,  # (F, 16) int32 flow-state table
    *,
    block: int,
    interpret: bool,
) -> jax.Array:
    p = slots.shape[0]
    f = init_state.shape[0]
    assert p % block == 0, (p, block)
    n_blocks = p // block
    a = jnp.take(meta, program[:, 1], axis=1)  # (P, 16) meta operand per lane
    ctrl = jnp.stack([program[:, 0], program[:, 2]])  # (2, 16) opcode, hist_src
    kernel = functools.partial(_flow_kernel, block=block, n_blocks=n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((2, N_LANES), lambda i, s: (0, 0)),
            pl.BlockSpec((block, N_LANES), lambda i, s: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((f, N_LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((f, N_LANES), jnp.int32),
        input_output_aliases={3: 0},  # (slots, ctrl, a, state): state -> out
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slots, ctrl, a, init_state)
