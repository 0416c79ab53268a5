"""Jit'd wrapper for the flow-feature kernel + the standard micro-op programs
that derive the paper's whole feature set (Table 7) from the meta set (Table 2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.util import round_up
from repro.kernels.flow_features import flow_features as _k

# Meta register layout (int32 lanes; paper: 13-byte register, see DESIGN.md for
# the 8-bit -> 32-bit lane adaptation).
META = {
    "pkt_size": 0,
    "arv_intv": 1,  # inter-arrival time (us); 0 for the first packet of a flow
    "dir": 2,  # 0/1
    "flags": 3,  # TCP/UDP/ICMP flags
    "ts": 4,  # arrival timestamp (us, truncated)
    "payload_len": 5,
    "one": 6,  # constant 1
    "zero": 7,  # constant 0
    "size_fwd": 8,  # pkt_size if dir==0 else 0
    "size_bwd": 9,  # pkt_size if dir==1 else 0
    "neg_pkt_size": 10,
    "neg_arv_intv": 11,
    "proto": 12,
}
META_WIDTH = 13

MICRO_OPS = {"nop": 0, "wr": 1, "add": 2, "sub": 3, "max": 4, "min": 5, "inc": 6}

# History-register (flow-state word) layout: 16 int32 lanes.
HIST = {
    "flow_dur": 0,  # sum of arv_intv                     (Table 7: #9)
    "pkt_count": 1,  # total number of packets            (#36)
    "flow_size": 2,  # sum of pkt_size                    (#6)
    "max_size": 3,  # max packet length                   (#11)
    "min_size": 4,  # min packet length                   (#12)
    "max_intv": 5,  # max inter-arrival                   (#19)
    "min_intv": 6,  # min inter-arrival                   (#20)
    "last_ts": 7,  # timestamp of latest packet (tracker state)
    "size_fwd": 8,  # per-direction flow size             (#7)
    "size_bwd": 9,
    "flags_acc": 10,  # accumulated flags                 (#28)
    "last_size": 11,
    "payload_bytes": 12,  # sum of payload_len            (#1-ish)
    "proto": 13,  # protocol type                         (#8)
    "spare14": 14,
    "spare15": 15,
}


def default_program_np() -> np.ndarray:
    """Host-side (numpy) twin of :func:`default_program` — usable inside jit
    traces for program-identity checks without creating traced constants."""
    O, M, H = MICRO_OPS, META, HIST
    rows = [
        (O["add"], M["arv_intv"], H["flow_dur"]),
        (O["inc"], M["zero"], H["pkt_count"]),
        (O["add"], M["pkt_size"], H["flow_size"]),
        (O["max"], M["pkt_size"], H["max_size"]),
        (O["min"], M["pkt_size"], H["min_size"]),
        (O["max"], M["arv_intv"], H["max_intv"]),
        (O["min"], M["arv_intv"], H["min_intv"]),
        (O["wr"], M["ts"], H["last_ts"]),
        (O["add"], M["size_fwd"], H["size_fwd"]),
        (O["add"], M["size_bwd"], H["size_bwd"]),
        (O["add"], M["flags"], H["flags_acc"]),
        (O["wr"], M["pkt_size"], H["last_size"]),
        (O["add"], M["payload_len"], H["payload_bytes"]),
        (O["wr"], M["proto"], H["proto"]),
        (O["nop"], M["zero"], H["spare14"]),
        (O["nop"], M["zero"], H["spare15"]),
    ]
    return np.array(rows, dtype=np.int32)


def default_program() -> jax.Array:
    """The micro-op program deriving the standard flow features (Table 7
    subset) from the meta set — one row per output lane: [op, meta_src, hist_src]."""
    return jnp.asarray(default_program_np())


def fold_features(
    program: jax.Array,
    slots: jax.Array,
    meta: jax.Array,
    feats: jax.Array,
    *,
    keep: jax.Array | None = None,
    block: int = 256,
    interpret: bool,
) -> jax.Array:
    """Fold a packet stream into a (F, 16) feature table through the Pallas
    ALU-cluster kernel, optionally dropping packets.

    ``keep`` (when given) is a (P,) bool mask: packets with ``keep == False``
    are redirected to a scratch row appended to the table, so they cannot
    touch any real flow's state (``wr``/``min`` lanes would otherwise corrupt
    it — zeroed meta is *not* a no-op).  This is how the tracker paths replay
    only the packets after a flow's last establish/evict event."""
    f = feats.shape[0]
    block = max(1, min(block, slots.shape[0]))
    if keep is None:
        return flow_feature_update(program, slots, meta, feats, block=block,
                                   interpret=interpret)
    ext = jnp.concatenate([feats, jnp.zeros((1, feats.shape[1]), jnp.int32)])
    out = flow_feature_update(program, jnp.where(keep, slots, f), meta, ext,
                              block=block, interpret=interpret)
    return out[:f]


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def flow_feature_update(
    program: jax.Array,
    slots: jax.Array,
    meta: jax.Array,
    init_state: jax.Array,
    *,
    block: int = 256,
    interpret: bool,
) -> jax.Array:
    """Fold a packet stream into the flow-state table.  Pads the packet axis
    with no-op packets (slot pointing at a scratch row)."""
    p = slots.shape[0]
    f = init_state.shape[0]
    pp = round_up(max(p, 1), block)
    if pp == p:
        return _k.flow_update(program, slots, meta, init_state, block=block,
                              interpret=interpret)
    # pad with packets aimed at a dedicated scratch row appended to the table
    # (so 'wr'/'add' lanes never corrupt a real flow's state)
    pad = pp - p
    slots = jnp.concatenate([slots, jnp.full((pad,), f, jnp.int32)])
    meta = jnp.concatenate([meta, jnp.zeros((pad, meta.shape[1]), jnp.int32)])
    state_ext = jnp.concatenate([init_state, jnp.zeros((1, init_state.shape[1]),
                                                       jnp.int32)])
    out = _k.flow_update(program, slots, meta, state_ext, block=block,
                         interpret=interpret)
    return out[:f]
