"""Work the served step needs, counted from shapes and the run's own
counters, and the device peaks it is held against.

Counts are algorithmic: the multiply-adds of each model on the rows that
carry work (judged packets, drained flows; not bucket padding or empty
drain rows), and the bytes the step must move (each packet's record in and
verdict out, each table slot a batch touches read and written once, each
record that moved to or from the cold tier read and written once, and the
weights read once per dispatch).  The whole table is not counted.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import models

PEAKS = Path(__file__).resolve().parent / "peaks.json"
INT32 = 4
SCALAR_FIELDS = 6  # ts, size, dir, flags, proto, tuple_hash


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Peak rates of ``device_kind``; an unknown device is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def mlp_flops(dims) -> int:
    """FLOPs of one packet through the MLP (2 per multiply-add)."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def flow_flops(fm: dict) -> int:
    """FLOPs of one flow through the flow model (2 per multiply-add)."""
    if fm["kind"] == "cnn":
        ch, k, L = fm["channels"], fm["kernel"], fm["seq"]
        f = 0
        for ci, co in zip(ch[:-1], ch[1:]):
            f += 2 * L * k * ci * co
            L = -(-L // 2)
        return f + 2 * L * ch[-1] * fm["fc"] + 2 * fm["fc"] * fm["classes"]
    if fm["kind"] == "transformer":
        P, B, D, M = fm["packets"], fm["bytes"], fm["d_k"], fm["mlp"]
        qkv = 3 * 2 * P * B * D
        attn = 2 * P * P * D * 2  # scores and the weighted sum
        mlp = 2 * P * D * M * 2
        return qkv + attn + mlp + 2 * D * fm["classes"]
    raise ValueError(f"unknown flow model {fm['kind']!r}")


def record_bytes(cfg: dict) -> int:
    """Bytes of one hot-table slot record: tuple id, count, last timestamp,
    the 16-lane history word, series and sizes (top_n each) and the payload
    matrix (top_k x pay_bytes)."""
    return INT32 * (3 + 16 + 2 * cfg["top_n"] + cfg["top_k"] * cfg["pay_bytes"])


def weight_bytes(cfg: dict) -> int:
    n = 0
    for group in models.shapes(cfg).values():
        for shape in group.values():
            k = 1
            for d in shape:
                k *= d
            n += k
    return INT32 * n


def dispatch_work(cfg: dict, *, packets: int, slots: int, flows: int,
                  cold_moves: int) -> tuple[float, float]:
    """(FLOPs, bytes) one dispatch needs: ``packets`` judged, ``slots``
    distinct table slots touched, ``flows`` drained and classified,
    ``cold_moves`` records spilled to or promoted from the cold tier."""
    flops = (packets * mlp_flops(cfg["packet_model"]["dims"])
             + flows * flow_flops(cfg["flow_model"]))
    rec = record_bytes(cfg)
    pkt_in = INT32 * (SCALAR_FIELDS + cfg["pay_bytes"])
    bytes_ = (packets * (pkt_in + INT32)  # record in, verdict out
              + 2 * slots * rec + 2 * cold_moves * (rec + INT32)
              + weight_bytes(cfg))
    return float(flops), float(bytes_)


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """Least time for the work at the peaks, and which of the two bounds it."""
    tc = flops / peak["bf16_flops"]
    tm = bytes_ / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
