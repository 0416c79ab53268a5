"""Int8 quantized engine datapath (quant-diff tier).

Four layers of guarantees:
  * kernel/oracle exactness — every execution path (Pallas arype/vpe, router
    emulate, router native) reproduces the NumPy int32 oracle bit-for-bit,
    per-tensor and per-output-channel;
  * routing fallbacks — a missing table entry, a missing table, or a
    scale-less artifact all degrade to the f32 path exactly (never
    mis-scaled int8), with the calibrated() warning;
  * calibration artifacts — scales round-trip through the backend-keyed
    artifact; corrupt/missing/schema-mismatched artifacts warn and fall back;
  * the differential harness — on a seeded traffic stream the quantized
    pipeline's decision flips stay within 1% of the f32 oracle and tracker
    state stays bit-exact (only engine outputs quantize).
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import router
from repro.kernels.arype_matmul import arype_matmul_q, ref_matmul, ref_quantized_matmul
from repro.kernels.vpe_smallmm import vpe_matmul_q
from repro.runtime import (
    QuantScales,
    RoutePlan,
    RuntimeConfig,
    autotune,
    platform,
    record_scales,
    runtime_overrides,
)
from repro.runtime import quant
from repro.runtime.autotune import Calibration, load_calibration, save_calibration

FLIP_BOUND = 0.01  # the acceptance bound for the seeded-stream differential


def _operands(m, k, n, seed=0, lo=-3.0, hi=3.0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(lo, hi, (m, k)).astype(np.float32))
    w = jnp.asarray(rng.uniform(-1.0, 1.0, (k, n)).astype(np.float32))
    return x, w


def _scales_for(x, w, per_channel=False):
    sx = quant.pick_scale(float(jnp.max(jnp.abs(x))))
    if per_channel:
        sw = tuple(quant.pick_scale(float(v))
                   for v in jnp.max(jnp.abs(w), axis=0))
    else:
        sw = quant.pick_scale(float(jnp.max(jnp.abs(w))))
    return sx, sw


@pytest.fixture(scope="module")
def fitted_scales():
    """One traffic-sample calibration shared by the slow differential tests."""
    from repro.launch.calibrate import calibrate_quant_scales

    return calibrate_quant_scales(steps=16, flow_models=("cnn",))


# ---------------------------------------------------------------------------
# Kernel vs oracle: bit-exact on non-aligned shapes, both scale layouts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("per_channel", [False, True])
@pytest.mark.parametrize("activation", ["none", "relu"])
@pytest.mark.parametrize("shape", [(7, 13, 5), (32, 64, 162), (130, 200, 96)])
def test_arype_q_matches_int32_oracle(shape, activation, per_channel):
    x, w = _operands(*shape)
    sx, sw = _scales_for(x, w, per_channel)
    got = arype_matmul_q(x, w, scale_x=sx, scale_w=sw, activation=activation,
                         interpret=True)
    want = ref_quantized_matmul(x, w, scale_x=sx, scale_w=sw, activation=activation)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("per_channel", [False, True])
@pytest.mark.parametrize("shape", [(5, 3, 8), (33, 20, 12)])
def test_vpe_q_matches_int32_oracle(shape, per_channel):
    x, w = _operands(*shape, seed=1)
    sx, sw = _scales_for(x, w, per_channel)
    got = vpe_matmul_q(x, w, scale_x=sx, scale_w=sw, activation="relu",
                       interpret=True)
    want = ref_quantized_matmul(x, w, scale_x=sx, scale_w=sw, activation="relu")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("per_channel", [False, True])
def test_router_impls_all_bit_exact(per_channel):
    """emulate (f32 lanes), native (int8/int32) and the Pallas kernels must
    agree with the oracle bit-for-bit — the f32-int emulation claim."""
    x, w = _operands(24, 48, 32, seed=2)
    sx, sw = _scales_for(x, w, per_channel)
    scales = QuantScales(entries=(("L", sx, sw),))
    want = np.asarray(ref_quantized_matmul(x, w, scale_x=sx, scale_w=sw,
                                           activation="relu"))
    for overrides in ({"quant_impl": "emulate"}, {"quant_impl": "native"},
                      {"use_pallas": True}):
        with runtime_overrides(quantize=True, quant_scales=scales, **overrides):
            got = np.asarray(router.matmul(x, w, name="L", activation="relu"))
        np.testing.assert_array_equal(got, want)


def test_dequant_error_is_scale_bounded():
    """|int8 - f32| per element is bounded by the two rounding half-steps."""
    x, w = _operands(64, 128, 32, seed=3)
    sx, sw = _scales_for(x, w, per_channel=True)
    q = np.asarray(ref_quantized_matmul(x, w, scale_x=sx, scale_w=sw))
    f = np.asarray(ref_matmul(x, w))
    k = x.shape[1]
    # worst case: every product off by (sx/2)|w| + (sw/2)|x| + cross term
    bound = k * (sx * 1.0 / 2 + max(sw) * 3.0 / 2 + sx * max(sw) / 4)
    assert np.max(np.abs(q - f)) <= bound


def test_resolve_quant_impl_policy():
    cfg = RuntimeConfig(quant_impl="auto")
    on_cpu = platform.backend() == "cpu"
    assert router._resolve_quant_impl(cfg, k=64) == (
        "emulate" if on_cpu else "native")
    # past the exact-emulation depth the int32 path is forced
    assert router._resolve_quant_impl(cfg, k=quant.EMULATE_MAX_K + 1) == "native"
    assert router._resolve_quant_impl(
        RuntimeConfig(quant_impl="native"), k=64) == "native"


# ---------------------------------------------------------------------------
# Routing fallbacks: quantize never silently mis-scales
# ---------------------------------------------------------------------------

def test_unknown_layer_name_stays_f32():
    x, w = _operands(16, 24, 8, seed=4)
    scales = QuantScales(entries=(("somebody_else", 0.1, 0.2),))
    with runtime_overrides(quantize=False):
        want = np.asarray(router.matmul(x, w, name="w0"))
    with runtime_overrides(quantize=True, quant_scales=scales):
        got = np.asarray(router.matmul(x, w, name="w0"))
    np.testing.assert_array_equal(got, want)


def test_quantize_without_table_stays_f32():
    x, w = _operands(16, 24, 8, seed=5)
    with runtime_overrides(quantize=False):
        want = np.asarray(router.matmul(x, w, name="w0"))
    with runtime_overrides(quantize=True, quant_scales=None):
        got = np.asarray(router.matmul(x, w, name="w0"))
    np.testing.assert_array_equal(got, want)


def test_scoped_lookup_prefers_scope_then_tail():
    scales = QuantScales(entries=(("pkt/w0", 0.1, 0.2), ("w1", 0.3, 0.4)))
    assert scales.lookup("w0", scope="pkt/") == (0.1, 0.2)
    assert scales.lookup("w0") is None
    assert scales.lookup("flow/w1") == (0.3, 0.4)


# ---------------------------------------------------------------------------
# Config + table validation
# ---------------------------------------------------------------------------

def test_invalid_quant_impl_rejected():
    with pytest.raises(ValueError, match="quant_impl"):
        RuntimeConfig(quant_impl="int4")


def test_scale_table_validation():
    with pytest.raises(ValueError, match="duplicate"):
        QuantScales(entries=(("a", 0.1, 0.1), ("a", 0.2, 0.2)))
    with pytest.raises(ValueError, match="positive"):
        QuantScales(entries=(("a", 0.0, 0.1),))
    with pytest.raises(ValueError, match="positive"):
        QuantScales(entries=(("a", 0.1, (0.1, -0.5)),))
    with pytest.raises(ValueError, match="layer name"):
        QuantScales(entries=(("", 0.1, 0.1),))


def test_fingerprint_is_stable_and_content_keyed():
    a = QuantScales(entries=(("w0", 0.1, (0.2, 0.3)),))
    b = QuantScales(entries=(("w0", 0.1, (0.2, 0.3)),))
    c = QuantScales(entries=(("w0", 0.1, (0.2, 0.31)),))
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != c.fingerprint
    assert a.fingerprint.startswith("int8/")


def test_dict_roundtrip_preserves_channel_scales():
    a = QuantScales(entries=(("w0", 0.1, (0.2, 0.3)), ("fc", 0.4, 0.5)))
    b = QuantScales.from_dict(json.loads(json.dumps(a.to_dict())))
    assert a == b and a.fingerprint == b.fingerprint
    assert isinstance(b.lookup("w0")[1], tuple)


def test_subset_restricts_lookup():
    a = QuantScales(entries=(("w0", 0.1, 0.2), ("w1", 0.3, 0.4)))
    s = a.subset(("w1",))
    assert s.names() == ("w1",) and s.lookup("w0") is None


def test_recorder_is_eager_only_and_per_channel():
    x, w = _operands(8, 6, 4, seed=6)
    with record_scales() as rec:
        router.matmul(x, w, name="eager_layer")
        jax.jit(lambda a, b: router.matmul(a, b, name="traced_layer"))(x, w)
    assert "eager_layer" in rec.stats and "traced_layer" not in rec.stats
    mx, mw = rec.stats["eager_layer"]
    assert mx == pytest.approx(float(jnp.max(jnp.abs(x))))
    assert len(mw) == 4  # one stat per output channel
    table = rec.scales()
    assert isinstance(table.lookup("eager_layer")[1], tuple)


# ---------------------------------------------------------------------------
# Plan/explain surface quantized placement
# ---------------------------------------------------------------------------

def test_plan_reports_quantized_layers():
    scales = QuantScales(entries=(("w0", 0.1, 0.2),))
    cfg = RuntimeConfig(quantize=True, quant_scales=scales)
    layers = [("w0", 8, 6, 12), ("w1", 8, 12, 6)]
    plan = RoutePlan.from_layers(layers, config=cfg)
    by_name = {s.name: s for s in plan.steps}
    assert by_name["w0"].quantized and not by_name["w1"].quantized
    text = plan.explain()
    assert "int8" in text and scales.fingerprint in text
    # f32 plans stay quiet about quantization
    assert "int8" not in RoutePlan.from_layers(layers).explain()


# ---------------------------------------------------------------------------
# Artifact flow: scales travel with the calibration, guarded like the rest
# ---------------------------------------------------------------------------

def _calib(**kw):
    return Calibration(tau=0.5, vpe_max_elems=1 << 20,
                       fingerprint=dict(platform.fingerprint()), **kw)


def test_artifact_roundtrip_with_scales(tmp_path):
    scales = QuantScales(entries=(("w0", 0.1, (0.2, 0.3)),))
    path = save_calibration(_calib(quant_scales=scales),
                            str(tmp_path / "calib.json"))
    loaded = load_calibration(path)
    assert loaded.quant_scales == scales
    cfg = loaded.apply(RuntimeConfig())
    # scales travel along, running int8 stays an explicit opt-in
    assert cfg.quant_scales == scales and cfg.quantize is False
    on = RuntimeConfig.calibrated(path, quantize=True)
    assert on.quantize is True and on.quant_scales == scales


def test_calibrated_quantize_without_scales_warns_and_stays_f32(tmp_path):
    path = save_calibration(_calib(), str(tmp_path / "calib.json"))
    with pytest.warns(UserWarning, match="no quant_scales"):
        cfg = RuntimeConfig.calibrated(path, quantize=True)
    assert cfg.quantize is False and cfg.quant_scales is None


def test_calibrated_quantize_missing_artifact_warns_and_stays_f32(tmp_path):
    with pytest.warns(UserWarning) as rec:
        cfg = RuntimeConfig.calibrated(str(tmp_path / "nope.json"),
                                       quantize=True)
    msgs = [str(w.message) for w in rec]
    assert any("no calibration artifact" in m for m in msgs)
    assert any("no quant_scales" in m for m in msgs)
    assert cfg.quantize is False


def test_corrupt_scale_entries_reject_artifact(tmp_path):
    path = save_calibration(_calib(), str(tmp_path / "calib.json"))
    raw = json.load(open(path))
    raw["quant_scales"] = {"entries": [["w0", -1.0, 0.5]]}  # negative scale
    with open(path, "w") as f:
        json.dump(raw, f)
    with pytest.warns(UserWarning, match="malformed"):
        assert load_calibration(path) is None
    with pytest.warns(UserWarning):
        cfg = RuntimeConfig.calibrated(path, quantize=True)
    assert cfg.quantize is False and cfg.quant_scales is None


def test_garbage_scale_block_rejects_artifact(tmp_path):
    path = save_calibration(_calib(), str(tmp_path / "calib.json"))
    raw = json.load(open(path))
    raw["quant_scales"] = {"entries": "garbage"}
    with open(path, "w") as f:
        json.dump(raw, f)
    with pytest.warns(UserWarning, match="malformed"):
        assert load_calibration(path) is None


def test_schema_mismatch_still_rejects_scaled_artifact(tmp_path):
    scales = QuantScales(entries=(("w0", 0.1, 0.2),))
    path = save_calibration(_calib(quant_scales=scales),
                            str(tmp_path / "calib.json"))
    raw = json.load(open(path))
    raw["schema_version"] = autotune.SCHEMA_VERSION + 1
    with open(path, "w") as f:
        json.dump(raw, f)
    with pytest.warns(UserWarning, match="schema_version"):
        assert load_calibration(path) is None


# ---------------------------------------------------------------------------
# Calibration pass + the seeded-stream differential (the acceptance harness)
# ---------------------------------------------------------------------------

def test_calibration_covers_engine_layers():
    """The unpruned fit must carry a scale for every routed engine matmul."""
    from repro.launch.calibrate import calibrate_quant_scales

    table = calibrate_quant_scales(steps=6, flow_models=("cnn",),
                                   max_flip_rate=None)
    names = set(table.names())
    assert {"w0", "w1", "w2", "w3"} <= names  # packet MLP
    assert {"conv1", "conv2", "conv3", "fc", "linear"} <= names  # flow CNN
    for n in names:
        sx, sw = table.lookup(n)
        assert sx > 0 and (sw > 0 if isinstance(sw, float)
                           else all(s > 0 for s in sw))


def test_sensitivity_pruning_respects_flip_budget(fitted_scales):
    """The pruned table keeps real coverage — the MAC-heavy CNN tail must
    survive — and prunes only whole layers (subset of the full fit)."""
    assert len(fitted_scales.entries) >= 3
    assert {"conv2", "conv3", "fc"} & set(fitted_scales.names())


@pytest.mark.parametrize("flow_model", ["cnn", "transformer"])
def test_differential_flips_bounded_and_tracker_exact(fitted_scales, flow_model):
    from repro.launch.calibrate import quant_divergence_report

    text, m = quant_divergence_report(fitted_scales, steps=8,
                                      flow_model=flow_model)
    assert m["tracker_bit_exact"], text
    assert m["pkt_flip_rate"] <= FLIP_BOUND, text
    assert m["flow_flip_rate"] <= FLIP_BOUND, text
    assert m["pkt_total"] > 0
    # the CLI-facing report must surface the flip counts
    assert "decision flips:" in text and "tracker state bit-exact: yes" in text
    assert f"pkt {m['pkt_flips']}/{m['pkt_total']}" in text


def test_quantized_pipeline_runs_under_masked_service(fitted_scales):
    """The serving frontend's pre-warmed masked buckets must dispatch the
    quantized pipeline unchanged (no retraces, all requests served)."""
    import asyncio

    from repro.data.traffic import TrafficConfig, TrafficGenerator
    from repro.models import paper_models
    from repro.serving import (
        OctopusPipeline,
        OctopusService,
        PipelineConfig,
        ServiceConfig,
        serve_stream,
    )

    pkt = paper_models.init_paper_model("mlp", jax.random.PRNGKey(0))
    flow = paper_models.init_paper_model("cnn", jax.random.PRNGKey(1))
    with runtime_overrides(quantize=True, quant_scales=fitted_scales):
        pipe = OctopusPipeline(pkt, flow, PipelineConfig(
            batch_size=32, max_ready=8, flow_model="cnn", table_size=128))
    gen = TrafficGenerator(TrafficConfig(batch_size=16, active_flows=8,
                                         table_size=128, seed=3))

    async def drive():
        async with OctopusService(pipe, ServiceConfig(buckets=(16, 32))) as svc:
            warm = svc.trace_count
            await serve_stream(svc, gen, requests=6)
            return svc.stats, svc.trace_count - warm

    stats, retraces = asyncio.run(drive())
    assert stats.served_requests == 6 and retraces == 0
    assert pipe.runtime.quantize and pipe.runtime.quant_scales is not None


def test_no_warnings_on_quantized_happy_path(fitted_scales):
    x, w = _operands(8, 6, 12, seed=7)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with runtime_overrides(quantize=True, quant_scales=fitted_scales):
            router.matmul(x, w, name="w0")
