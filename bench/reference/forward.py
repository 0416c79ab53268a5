"""Plain forward passes of the three Octopus models (arXiv:2308.11312,
section 4.2) in numpy float32, with no JAX and nothing of the system under
test: the packet MLP (use case 1), the flow 1D-CNN (use case 2) and the
payload transformer (use case 3), plus the per-packet feature vector and the
flow-model input preparation they read.

``precision`` selects the arithmetic.  ``"f32"`` is the reference.  The
lower ones round as a cheaper datapath would, and serve as controls that
the comparison must tell apart from a sound run:

* ``"bf16"``: every input, weight and intermediate rounded to bfloat16
  (accumulation in float32, result rounded back to bfloat16);
* ``"fp8"``: matmul operands rounded to float8 e4m3 (accumulation and
  elementwise work in float32).

``"ops:<bits>"`` is float32 with the operands of matmul ``j`` (in the
order the model applies them) rounded to bfloat16 where bit ``j`` is 1:
what a TPU computes for a float32 matmul at DEFAULT precision on the MXU,
while one that runs as multiply and add on the vector unit stays float32.
``operand_roundings`` lists every such choice for a flow model.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

PRECISIONS = ("f32", "bf16", "fp8")


def _round(x: np.ndarray, precision: str, *, operand: bool) -> np.ndarray:
    x = np.asarray(x, np.float32)
    if precision == "bf16":
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    if precision == "fp8" and operand:
        return x.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
    return x


def _mm(x: np.ndarray, w: np.ndarray, precision: str, j: int = 0) -> np.ndarray:
    """Matmul ``j`` of the model at ``precision``."""
    if precision.startswith("ops:"):
        if precision[4 + j] == "1":
            x, w = (np.asarray(a, np.float32).astype(ml_dtypes.bfloat16)
                    .astype(np.float32) for a in (x, w))
        return np.matmul(x, w, dtype=np.float32)
    x = _round(x, precision, operand=True)
    w = _round(w, precision, operand=True)
    return _round(np.matmul(x, w, dtype=np.float32), precision, operand=False)


def _act(x: np.ndarray, precision: str) -> np.ndarray:
    return _round(x, precision, operand=False)


def packet_features(size, dirs, flags, proto, pay_bytes: int) -> np.ndarray:
    """Use case 1's six per-packet inputs: size, direction, flags, protocol,
    payload length (size capped at the payload bytes kept), inter-arrival
    (always 0 at packet granularity)."""
    size = np.asarray(size)
    return np.stack([size, np.asarray(dirs), np.asarray(flags),
                     np.asarray(proto), np.minimum(size, pay_bytes),
                     np.zeros_like(size)], axis=-1).astype(np.float32)


def mlp(params: dict, x: np.ndarray, precision: str = "f32") -> np.ndarray:
    """6 -> 12 -> 6 -> 3 -> 2, ReLU between layers; returns logits."""
    h = _act(x, precision)
    n = sum(1 for k in params if k.startswith("w"))
    for i in range(n):
        h = _act(_mm(h, params[f"w{i}"], precision, i) + params[f"b{i}"], precision)
        if i < n - 1:
            h = np.maximum(h, 0.0)
    return h


def _same_cols(h: np.ndarray, k: int) -> np.ndarray:
    """(F, L, C) -> (F, L, k*C): the k-tap window around each position,
    zero padded ('same' convolution as a matmul)."""
    pad = k // 2
    hp = np.pad(h, ((0, 0), (pad, pad), (0, 0)))
    return np.concatenate([hp[:, i:i + h.shape[1]] for i in range(k)], axis=-1)


def _pool2(h: np.ndarray) -> np.ndarray:
    """Max-pool by 2 along the sequence, keeping a trailing odd element."""
    L = h.shape[1]
    if L % 2:
        h = np.concatenate([h, np.full_like(h[:, :1], -np.inf)], axis=1)
    return h.reshape(h.shape[0], -1, 2, h.shape[2]).max(axis=2)


def flow_series_input(series: np.ndarray) -> np.ndarray:
    """The CNN's input: log(1 + inter-arrival) of the first top-n packets."""
    return np.log1p(np.asarray(series, np.float32)).astype(np.float32)


def cnn(params: dict, x: np.ndarray, precision: str = "f32") -> np.ndarray:
    """(F, 20) -> three conv(k=3) + ReLU + max-pool/2 blocks (20->10->5->3),
    flatten (3 x 32), FC 128 + ReLU, linear to the classes; returns logits."""
    h = _act(x, precision)[:, :, None]
    layers = sum(1 for k in params if k.startswith("convb"))
    for i in range(layers):
        w = params[f"conv{i}"]
        k = w.shape[0] // h.shape[2]
        h = _act(_mm(_same_cols(h, k), w, precision, i) + params[f"convb{i}"],
                 precision)
        h = _pool2(np.maximum(h, 0.0))
    h = h.reshape(h.shape[0], -1)
    h = np.maximum(_act(_mm(h, params["fc_w"], precision, layers) + params["fc_b"],
                        precision), 0.0)
    return _act(_mm(h, params["out_w"], precision, layers + 1) + params["out_b"],
                precision)


def flow_payload_input(payload: np.ndarray) -> np.ndarray:
    """The transformer's input: payload bytes scaled to [0, 1]."""
    return (np.asarray(payload, np.float32) / np.float32(255.0)).astype(np.float32)


def _softmax(x: np.ndarray, axis: int = -1) -> np.ndarray:
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def transformer(params: dict, x: np.ndarray, precision: str = "f32") -> np.ndarray:
    """(F, 15, 16) -> single-head self-attention (d_k 64), MLP 64->128->64,
    mean over packets, linear to the classes; returns logits."""
    x = _act(x, precision)
    q = _mm(x, params["wq"], precision, 0)
    k = _mm(x, params["wk"], precision, 1)
    v = _mm(x, params["wv"], precision, 2)
    d_k = params["wq"].shape[1]
    s = _act(_mm(q, np.swapaxes(k, 1, 2), precision, 3) / np.float32(np.sqrt(d_k)),
             precision)
    a = _act(_softmax(s), precision)
    h = _mm(a, v, precision, 4)
    h = np.maximum(_act(_mm(h, params["mlp1"], precision, 5) + params["mlp1_b"],
                        precision), 0.0)
    h = _act(_mm(h, params["mlp2"], precision, 6) + params["mlp2_b"], precision)
    pooled = _act(h.mean(axis=1), precision)
    return _act(_mm(pooled, params["cls_w"], precision, 7) + params["cls_b"],
                precision)


def softmax(logits: np.ndarray) -> np.ndarray:
    return _softmax(np.asarray(logits, np.float64))


def operand_roundings(kind: str, params: dict) -> list[str]:
    """Every ``"ops:<bits>"`` precision of a flow model: each of its
    matmuls with float32 or bfloat16 operands."""
    n = 8 if kind == "transformer" else \
        sum(1 for k in params if k.startswith("convb")) + 2
    return [f"ops:{m:0{n}b}" for m in range(2 ** n)]


def flow_logits(kind: str, params: dict, series, payload,
                precision: str = "f32") -> np.ndarray:
    """Flow-model logits from a flow's tracker memories."""
    if kind == "cnn":
        return cnn(params, flow_series_input(series), precision)
    return transformer(params, flow_payload_input(payload), precision)
