"""The benchmark's plain references still agree with what they were copied
from: the oracle trackers of the repository's tests and the models'
float32 forward, on a small CPU case."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import check, models  # noqa: E402
from bench.reference import forward, oracle  # noqa: E402
from bench.traffic import generator  # noqa: E402

from repro.core import cold_store, flow_tracker  # noqa: E402
from repro.core.feature_extractor import packet_meta_features  # noqa: E402
from repro.models import paper_models  # noqa: E402
from repro.serving.packet_path import FlowEngine  # noqa: E402

TESTS = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(TESTS))

from test_cold_store import TwoLevelOracle  # noqa: E402
from test_pipeline import OracleTracker  # noqa: E402

MIX = {"ports_per_lane": 2, "popularity": {"kind": "zipf", "s": 1.0},
       "flow_size": {"kind": "uniform", "min_packets": 3, "max_packets": 30},
       "packet_size": {"sizes": [64, 594, 1518], "weights": [7, 4, 1]}}


def _batches(n_batches=40, per_port=16, flows=96, seed=3):
    s = generator.Streams(MIX, lanes=1, flows_per_lane=flows, seed=seed, pay_bytes=16)
    for _ in range(n_batches):
        b = s.block(per_port)
        yield {f: getattr(b, f).reshape((-1,) + getattr(b, f).shape[2:])
               for f in generator.Block.FIELDS}


def test_hashes_match_the_program():
    h = np.array([0, 1, 7, 2**31 - 1, -1, -2**31, 123456789], np.int32)
    got = flow_tracker.hash_slot(jnp.asarray(h), 8192)
    assert [oracle.hot_slot(int(x), 8192) for x in h] == np.asarray(got).tolist()
    a, b = cold_store.cold_slots(jnp.asarray(h), 4096)
    assert [oracle.cold_slots(int(x), 4096) for x in h] == list(
        zip(np.asarray(a).tolist(), np.asarray(b).tolist()))


@pytest.mark.parametrize("cold", [0, 64])
def test_oracle_copies_agree_with_their_sources(cold):
    args = (16, 20, 15, 16)
    if cold:
        mine = oracle.TwoLevelOracle(16, cold, 20, 15, 16)
        src = TwoLevelOracle(16, cold, 20, 15, 16)
    else:
        mine, src = oracle.OracleTracker(*args), OracleTracker(*args)
    drained = 0
    for pk in _batches():
        rows = check._as_dicts(pk)
        if cold:
            a, b = mine.step_batch(rows, 4), src.step_batch(rows, 4)
        else:
            for p in rows:
                src.process(p)
            a, b = mine.step_batch(rows, 4), src.drain_ready(4)
        assert a == b
        drained += len(a)
        assert mine.slots == src.slots
        if cold:
            assert mine.cold == src.cold
            assert (mine.spilled, mine.promoted, mine.tick) == (
                src.spilled, src.promoted, src.tick)
    assert drained > 0
    if cold:
        assert mine.spilled > 0 and mine.promoted > 0


@pytest.fixture(scope="module")
def weights():
    cfgs = {k: {"packet_model": {"dims": [6, 12, 6, 3, 2]}, "flow_model": fm}
            for k, fm in (("cnn", {"kind": "cnn", "seq": 20, "channels": [1, 32, 32, 32],
                                    "kernel": 3, "fc": 128, "classes": 162}),
                          ("transformer", {"kind": "transformer", "packets": 15, "bytes": 16,
                                           "d_k": 64, "mlp": 128, "classes": 162}))}
    return {k: jax.device_get(models.make_weights(c, 7)) for k, c in cfgs.items()}


def test_weights_have_the_program_shapes(weights):
    for kind, w in weights.items():
        specs = {"cnn": paper_models.cnn_specs, "transformer": paper_models.transformer_specs}
        want = {k: s.shape for k, s in specs[kind]().items()}
        assert {k: v.shape for k, v in w["flow"].items()} == want
        assert {k: v.shape for k, v in w["packet"].items()} == {
            k: s.shape for k, s in paper_models.mlp_specs().items()}


def test_packet_forward_agrees_with_the_program(weights):
    (pk,) = list(_batches(1, per_port=64))
    batch = flow_tracker.PacketBatch(**{f: jnp.asarray(v) for f, v in pk.items()})
    x = np.asarray(packet_meta_features(batch))
    np.testing.assert_array_equal(
        forward.packet_features(pk["size"], pk["dir"], pk["flags"], pk["proto"], 16), x)
    w = weights["cnn"]["packet"]
    np.testing.assert_allclose(forward.mlp(w, x), np.asarray(paper_models.mlp_apply(w, x)),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("kind", ["cnn", "transformer"])
def test_flow_forward_agrees_with_the_program(weights, kind):
    rng = np.random.default_rng(0)
    series = rng.integers(0, 5000, (32, 20)).astype(np.int32)
    series[:, 0] = 0
    payload = rng.integers(0, 256, (32, 15, 16)).astype(np.int32)
    w = weights[kind]["flow"]
    eng = FlowEngine(w, kind)
    want = np.asarray(eng.fn(w, eng.prep(jnp.asarray(series), jnp.asarray(payload))))
    got = forward.flow_logits(kind, w, series, payload)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("precision", ["bf16", "fp8"])
def test_lower_precisions_move_the_logits(weights, precision):
    rng = np.random.default_rng(1)
    series = rng.integers(0, 5000, (64, 20)).astype(np.int32)
    w = weights["cnn"]["flow"]
    ref = forward.flow_logits("cnn", w, series, None)
    low = forward.flow_logits("cnn", w, series, None, precision)
    rel = np.abs(low - ref).max() / np.abs(ref).max()
    # bf16 keeps 8 mantissa bits, e4m3 keeps 4
    assert (1e-4 < rel < 0.05) if precision == "bf16" else (rel > 0.01)


@pytest.mark.parametrize("kind", ["cnn", "transformer"])
def test_the_fit_finds_the_operand_rounding_that_served_the_scores(weights, kind):
    """Scores served at one per-matmul operand rounding fit that rounding to
    float32 rounding; scores of bfloat16 storage fit none."""
    rng = np.random.default_rng(2)
    series = rng.integers(0, 5000, (300, 20)).astype(np.int32)
    payload = rng.integers(0, 256, (300, 15, 16)).astype(np.int32)
    w = weights[kind]["flow"]
    roundings = forward.operand_roundings(kind, w)
    assert len(roundings) == 2 ** (5 if kind == "cnn" else 8)
    planted = roundings[len(roundings) // 2 + 3]
    for precision, fits in ((planted, True), ("bf16", False)):
        low = forward.flow_logits(kind, w, series, payload, precision)
        dev, fit = check._fitted_dev(kind, w, series, payload, low.argmax(axis=1),
                                     forward.softmax(low).max(axis=1).astype(np.float32))
        if fits:
            assert fit == planted and dev < 1e-5
        else:
            assert dev > 3e-4
