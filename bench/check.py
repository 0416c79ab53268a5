"""The comparison that decides ``correct``: what the timed path served,
against the plain references in ``bench/reference``.

* Tracker and drain: every dispatch's kept packets, in the order the
  clients submitted them, are replayed through the plain tracker (with the
  cold tier where the configuration has one; one tracker per lane where it
  has lanes).  Each dispatch's drained tuple ids, its spill and promote
  counts, and the final table state (every live slot's tuple, count, last
  timestamp, history word and memories; every cold entry) must be equal.
  The limit is 0.
* Packet engine and head: every request's verdicts against the reference
  MLP's.  The number is the widest gap by which a served verdict's
  reference logit lies below the reference's best, as a share of that
  packet's largest reference logit.
* Flow engine and head: every drained flow's class against the reference
  flow model on the reference tracker's memories (the same widest gap), and
  its score against the reference probability of the served class (the
  error of the log-probability, as a share of the row's largest reference
  logit): the widest error, and the mean over all drained flows.  The
  widest numbers catch a single wrong answer; the mean reads the precision
  the flow engine computed in, steady from seed to seed.
* Flow engine precision: the configurations state float32 at the TPU's
  DEFAULT matmul precision, under which each matmul may round its operands
  to bfloat16 (on the MXU) or keep them in float32 (as multiply and add on
  the vector unit), and nothing else is rounded.  ``score_dev_mean`` is the
  same mean score error against the reference at that precision, with the
  choice of operand rounding per matmul that fits the served scores best
  (fitted on a sample of the drained flows).  A sound run matches one
  choice to float32 rounding; bfloat16 storage or elementwise work, or
  float8 operands, match none.

``answers`` computes those numbers for the served answers, or for a
control in the program's place.
"""
from __future__ import annotations

import numpy as np

from bench.reference import forward
from bench.reference.oracle import OracleTracker, TwoLevelOracle

FIELDS = ("ts", "size", "dir", "flags", "proto", "tuple_hash", "payload")


def shard_of(h: np.ndarray, lanes: int) -> np.ndarray:
    """A flow's lane: its tuple hash as uint32, modulo the lane count."""
    return (np.asarray(h).astype(np.int64) & 0xFFFFFFFF) % lanes


def _as_dicts(pk: dict) -> list:
    cols = [pk[f].tolist() for f in FIELDS]
    return [dict(zip(FIELDS, row)) for row in zip(*cols)]


def _oracle(cfg: dict):
    if cfg["cold_size"]:
        return TwoLevelOracle(cfg["table_size"], cfg["cold_size"], cfg["top_n"],
                              cfg["top_k"], cfg["pay_bytes"], cfg["cold_policy"])
    return OracleTracker(cfg["table_size"], cfg["top_n"], cfg["top_k"],
                         cfg["pay_bytes"])


def _state_mismatch(hot: dict, cold, oracle) -> int:
    """Differences between one lane's final table and the plain tracker's."""
    bad = 0
    live = np.flatnonzero(hot["count"] > 0)
    bad += len(set(live.tolist()) ^ set(oracle.slots))
    for s in live.tolist():
        e = oracle.slots.get(s)
        if e is None:
            continue
        bad += int((hot["tuple_id"][s], hot["count"][s], hot["last_ts"][s])
                   != (e["tuple_id"], e["count"], e["last_ts"]))
        for name, want in (("features", oracle.feature_word(e)),
                           ("series", e["series"]), ("sizes", e["sizes"]),
                           ("payload", e["payload"])):
            bad += int(not np.array_equal(hot[name][s], np.asarray(want)))
    if cold is None:
        return bad
    occ = np.flatnonzero(cold["count"] > 0)
    bad += len(set(occ.tolist()) ^ set(oracle.cold))
    for c in occ.tolist():
        e = oracle.cold.get(c)
        if e is None:
            continue
        bad += int((cold["tuple_id"][c], cold["count"][c], cold["stamp"][c])
                   != (e["tuple_id"], e["count"], e["stamp"]))
        bad += int(not np.array_equal(cold["features"][c],
                                      np.asarray(oracle.feature_word(e))))
        for name in ("series", "sizes", "payload"):
            bad += int(not np.array_equal(cold[name][c], np.asarray(e[name])))
        bad += int(cold["last_ts"][c] != e["last_ts"])
    bad += int(int(cold["tick"]) != oracle.tick)
    return bad


def replay(cfg: dict, dispatches: list, final_state: dict) -> tuple[int, list]:
    """Replay the served dispatches through the plain tracker(s).

    ``dispatches``: per dispatch ``{"packets": {field: array}, "out": {...}}``
    with the kept packets in submission order and the step's outputs
    (``drained`` mask and tuple ids, ``spilled``, ``promoted``).  ``final_state``: the
    table after the last dispatch, ``{"hot": {...}, "cold": {...} or None}``
    with a leading lane axis.  Returns (mismatches, expected drained flows
    per dispatch as (dispatch, row, record))."""
    lanes = int(cfg["lanes"])
    per_lane = cfg["max_ready"] // lanes
    oracles = [_oracle(cfg) for _ in range(lanes)]
    bad, flows = 0, []
    for i, d in enumerate(dispatches):
        pk, out = d["packets"], d["out"]
        lane = shard_of(pk["tuple_hash"], lanes)
        spilled = promoted = 0
        for ln, orc in enumerate(oracles):
            sel = np.flatnonzero(lane == ln)
            s0 = getattr(orc, "spilled", 0)
            p0 = getattr(orc, "promoted", 0)
            want = orc.step_batch(_as_dicts({f: pk[f][sel] for f in FIELDS}),
                                  per_lane)
            spilled += getattr(orc, "spilled", 0) - s0
            promoted += getattr(orc, "promoted", 0) - p0
            rows = np.flatnonzero(out["drained"]["mask"][ln * per_lane:(ln + 1) * per_lane])
            bad += abs(len(rows) - len(want))
            for r, w in enumerate(want):
                row = ln * per_lane + r
                if r < len(rows):
                    bad += int(out["drained"]["tuple_id"][row] != w["tuple_id"])
                flows.append((i, row, w))
        bad += int(out["spilled"] != spilled) + int(out["promoted"] != promoted)
    for ln, orc in enumerate(oracles):
        hot = {k: v[ln] for k, v in final_state["hot"].items()}
        cold = None if final_state["cold"] is None else \
            {k: v[ln] for k, v in final_state["cold"].items()}
        bad += _state_mismatch(hot, cold, orc)
    return bad, flows


def _gap(ref_logits: np.ndarray, chosen: np.ndarray) -> float:
    """Widest gap by which the chosen class's reference logit lies below the
    reference's best, as a share of the row's largest reference logit
    magnitude (0 where every choice is the reference's).  Rounding moves a
    logit by a share of the row's scale, so the share reads the precision
    whatever the seed's weights make that scale."""
    if not len(chosen):
        return 0.0
    got = np.take_along_axis(ref_logits, chosen[:, None].astype(np.int64), 1)[:, 0]
    scale = np.maximum(np.abs(ref_logits).max(axis=1), np.finfo(np.float32).tiny)
    return float(((ref_logits.max(axis=1) - got) / scale).max())


def answers(cfg: dict, params: dict, requests: list, flows: list,
            served: dict | None, control: str = "", notes: dict | None = None) -> dict:
    """The compared numbers for the served answers (``served`` given) or,
    with ``served=None``, for a control put in the program's place: the
    reference at a lower precision (``"bf16"``, ``"fp8"``), or the
    reference with one answer altered where it is produced (``"altered"``:
    the first packet verdict of every request flipped, the first drained
    flow of every dispatch given the next class).

    ``requests``: ``{"packets": {...}, "actions": array}`` per answered
    request.  ``flows``: (dispatch, row, reference record) per drained flow.
    ``served``: ``{"flow_cls": [...], "flow_scores": [...]}`` per dispatch.
    ``notes``, where given, gets the fitted operand rounding."""
    pb = cfg["pay_bytes"]
    out = {"pkt_gap": 0.0, "flow_gap": 0.0, "score_err": 0.0, "score_mean_err": 0.0,
           "score_dev_mean": 0.0}
    if requests:
        pkt = {f: np.concatenate([r["packets"][f] for r in requests])
               for f in ("size", "dir", "flags", "proto")}
        x = forward.packet_features(pkt["size"], pkt["dir"], pkt["flags"],
                                    pkt["proto"], pb)
        ref = forward.mlp(params["packet"], x)
        if served is not None:
            chosen = np.concatenate([r["actions"] for r in requests])
        elif control == "altered":
            chosen = ref.argmax(axis=1)
            first = np.cumsum([0] + [len(r["actions"]) for r in requests[:-1]])
            chosen[first] = 1 - chosen[first]
        else:
            chosen = forward.mlp(params["packet"], x, control).argmax(axis=1)
        out["pkt_gap"] = _gap(ref, chosen)
    if flows:
        kind = cfg["flow_model"]["kind"]
        series = np.asarray([w["series"] for _, _, w in flows], np.int32)
        payload = np.asarray([w["payload"] for _, _, w in flows], np.int32)
        ref = forward.flow_logits(kind, params["flow"], series, payload)
        if served is not None:
            chosen = np.asarray([served["flow_cls"][i][r] for i, r, _ in flows])
            score = np.asarray([served["flow_scores"][i][r] for i, r, _ in flows])
        elif control == "altered":
            chosen = ref.argmax(axis=1)
            score = forward.softmax(ref).max(axis=1)
            disp = np.asarray([i for i, _, _ in flows])
            first = np.flatnonzero(np.r_[True, disp[1:] != disp[:-1]])
            chosen[first] = (chosen[first] + 1) % ref.shape[1]
        else:
            low = forward.flow_logits(kind, params["flow"], series, payload, control)
            chosen = low.argmax(axis=1)
            score = forward.softmax(low).max(axis=1)
        out["flow_gap"] = _gap(ref, chosen)
        err = _score_err(ref, chosen, score)
        out["score_err"] = float(err.max())
        out["score_mean_err"] = float(err.mean())
        out["score_dev_mean"], fit = _fitted_dev(kind, params["flow"], series, payload,
                                                 chosen, score)
        if notes is not None:
            notes["score_dev_fit"] = fit
    return out


def _score_err(ref: np.ndarray, chosen: np.ndarray, score: np.ndarray) -> np.ndarray:
    """Per flow, the served score against the reference probability of the
    served class, as the error of its log (a logit-sized quantity) over the
    row's largest reference logit: rounding reads as a share of the logits'
    scale, whatever the seed's weights make that scale."""
    lp = np.log(np.maximum(forward.softmax(ref), np.finfo(np.float64).tiny))
    got = np.take_along_axis(lp, chosen[:, None].astype(np.int64), 1)[:, 0]
    return np.abs(np.log(np.maximum(score.astype(np.float64), np.finfo(np.float64).tiny))
                  - got) / np.maximum(np.abs(ref).max(axis=1), np.finfo(np.float32).tiny)


FIT_FLOWS = 256  # drained flows the operand rounding is fitted on


def _fitted_dev(kind: str, params: dict, series, payload, chosen, score
                ) -> tuple[float, str]:
    """The mean score error against the reference at the stated precision
    whose per-matmul operand rounding fits a sample of the flows best, and
    that rounding."""
    sample = np.unique(np.linspace(0, len(chosen) - 1, FIT_FLOWS).astype(np.int64))

    def dev(precision, rows):
        ref = forward.flow_logits(kind, params, series[rows], payload[rows], precision)
        return float(_score_err(ref, chosen[rows], score[rows]).mean())

    fit = min(forward.operand_roundings(kind, params), key=lambda p: dev(p, sample))
    return dev(fit, slice(None)), fit
