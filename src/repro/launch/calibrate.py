"""Measure the arype/vpe crossover on this backend and persist it.

    PYTHONPATH=src python -m repro.launch.calibrate                 # cache path
    PYTHONPATH=src python -m repro.launch.calibrate --out calib.json
    PYTHONPATH=src python -m repro.launch.calibrate --smoke         # CI subset

Sweeps the (m, k, n) timing grid (``repro.runtime.autotune``), fits the
measured crossover into calibrated ``tau`` / ``vpe_max_elems``, writes the
backend-keyed artifact, then reports — per paper use-case model — every layer
whose placement under the calibrated thresholds diverges from the analytic
defaults (the full placements come from ``RoutePlan.explain``).

With ``--quant`` (on by default) the run also fits the int8 datapath's
per-layer scales from a seeded :class:`TrafficGenerator` sample pushed through
both engines (:func:`calibrate_quant_scales`), persists them in the same
artifact, and prints a decision-flip divergence report
(:func:`quant_divergence_report`) comparing the quantized pipeline against the
f32 oracle on the same stream.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Sequence, Tuple

from repro.core.collaborative import usecase2_layers, usecase3_layers
from repro.runtime import (
    DEFAULT_RUNTIME,
    QuantScales,
    RoutePlan,
    RuntimeConfig,
    autotune,
    platform,
)

# Paper-model matmul stacks the report diffs (MLP per-packet batch 8; the
# flow use-cases at 1000 tracked flows, the paper's Table 6 operating point).
_MLP_LAYERS = [("w0", 8, 6, 12), ("w1", 8, 12, 6), ("w2", 8, 6, 3), ("w3", 8, 3, 2)]


def _model_stacks(flows: int) -> list[tuple[str, list[tuple[str, int, int, int]]]]:
    return [
        ("usecase1_mlp(batch=8)", _MLP_LAYERS),
        (f"usecase2_cnn(flows={flows})", usecase2_layers(flows)),
        (f"usecase3_transformer(flows={flows})", usecase3_layers(flows)),
    ]


def divergence_report(calibrated: RuntimeConfig, *, flows: int = 1000,
                      analytic: RuntimeConfig = DEFAULT_RUNTIME,
                      verbose: bool = False) -> str:
    """Per paper-model layer, where calibrated placement diverges from the
    analytic default (and the full calibrated plan when ``verbose``)."""
    lines = []
    for label, layers in _model_stacks(flows):
        a_plan = RoutePlan.from_layers(layers, config=analytic)
        c_plan = RoutePlan.from_layers(layers, config=calibrated)
        moved = [(a, c) for a, c in zip(a_plan.steps, c_plan.steps)
                 if a.engine != c.engine]
        lines.append(f"{label}:")
        if not moved:
            lines.append("  placement unchanged by calibration")
        for a, c in moved:
            lines.append(f"  {a.name}  ({a.m},{a.k},{a.n})  "
                         f"{a.engine} -> {c.engine}  (util={c.route.util:.3f})")
        if verbose:
            lines.extend("  " + ln for ln in c_plan.explain().splitlines())
    return "\n".join(lines)


def _traffic_config(table_size: int = 256, seed: int = 7):
    from repro.data.traffic import TrafficConfig

    # Dense per-flow traffic (few concurrent flows sharing each microbatch)
    # so flows actually mature to ready within a short calibration drive —
    # the flow engines only ever classify drained (count >= top_n) flows, so
    # sparse traffic would leave the quant sample with no decision rows.
    return TrafficConfig(batch_size=32, active_flows=8, elephant_fraction=0.4,
                         table_size=table_size, seed=seed)


def calibrate_quant_scales(*, steps: int = 16, traffic=None,
                           flow_models: Sequence[str] = ("cnn", "transformer"),
                           max_flip_rate: float | None = 0.01,
                           ) -> QuantScales:
    """Fit per-layer symmetric int8 scales from a seeded traffic sample.

    Drives an f32 pipeline over ``steps`` :class:`TrafficGenerator`
    microbatches so the flow engines see *tracker-shaped* inputs (drained
    series/payload rows, not synthetic tensors), then replays the engine
    applications eagerly under :func:`repro.runtime.quant.record_scales` to
    collect max-abs statistics for every routed matmul — per-tensor for
    activations, per-output-channel for weights.

    When ``max_flip_rate`` is set, a greedy sensitivity pass then prunes the
    table per decision stream: for the packet MLP (allow/deny via
    :func:`decisions.decide_binary`) and each flow model (class argmax)
    independently, the layer whose removal most reduces that stream's
    decision flips on the calibration sample is dropped — an absent table
    entry routes to the f32 path at serve time — until the stream's sample
    flip rate is at or below the target.  The streams are independent models
    over disjoint layer sets, so per-stream pruning never trades one
    stream's accuracy against another's.  Returns the fitted (possibly
    pruned) :class:`QuantScales` table.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import decisions
    from repro.core.feature_extractor import packet_meta_features
    from repro.data.traffic import TrafficGenerator
    from repro.models import paper_models
    from repro.runtime import record_scales, resolve_config, runtime_overrides
    from repro.serving import OctopusPipeline, PipelineConfig

    tcfg = traffic if traffic is not None else _traffic_config()
    gen = TrafficGenerator(tcfg)
    batches = [gen.next_batch() for _ in range(steps)]
    pkt_params = paper_models.init_paper_model("mlp", jax.random.PRNGKey(0))
    pkt_x = jnp.concatenate([packet_meta_features(b) for b in batches], axis=0)
    flow_samples = []  # (apply_fn, flow_params, flow_x, real_rows) per model

    with runtime_overrides(quantize=False), record_scales() as rec:
        paper_models.mlp_apply(pkt_params, pkt_x)
        for model in flow_models:
            flow_params = paper_models.init_paper_model(model, jax.random.PRNGKey(1))
            pcfg = PipelineConfig(batch_size=tcfg.batch_size, max_ready=8,
                                  flow_model=model, table_size=tcfg.table_size)
            pipe = OctopusPipeline(pkt_params, flow_params, pcfg)
            top_n = pipe.state.series.shape[1]
            rows = []
            for b in batches:
                out = pipe.step(b)
                mask = np.asarray(out.drained.mask)
                if mask.any():
                    x = pipe.flow_engine.prep(out.drained.series,
                                              out.drained.payload)
                    rows.append(np.asarray(x)[mask])
                # Ready-but-not-yet-drained slots (past the max_ready cap)
                # are decision-eligible too — they classify as-is on a later
                # drain.  Immature slots are excluded: the engines never see
                # a flow before count >= top_n, so sampling half-filled
                # series would measure sensitivity on impossible inputs.
                ready = np.asarray(pipe.state.count) >= top_n
                if ready.any():
                    x = pipe.flow_engine.prep(pipe.state.series,
                                              pipe.state.payload)
                    rows.append(np.asarray(x)[ready])
            if rows:
                flow_x = jnp.asarray(np.concatenate(rows, axis=0))
            else:  # degenerate sample: fall back to a zero row (eps-guarded)
                shape = pipe.flow_engine.abstract_input(1).shape
                flow_x = jnp.zeros(shape, jnp.float32)
            apply_fn = (paper_models.cnn_apply if model == "cnn"
                        else paper_models.transformer_apply)
            flow_samples.append((apply_fn, flow_params, flow_x, bool(rows)))
            apply_fn(flow_params, flow_x)
    full = rec.scales()
    if max_flip_rate is None or not full.entries:
        return full

    # Greedy per-stream sensitivity pruning on the calibration sample.
    # Decisions are what the data plane acts on, so flips — not logit
    # error — are the cost.
    base = resolve_config(None).replace(quantize=False, quant_scales=None)

    def _stream_layers(fn, params, x) -> Tuple[str, ...]:
        with runtime_overrides(quantize=False), record_scales() as r:
            fn(params, x[:1], config=base)
        return tuple(r.stats)

    def _prune_stream(names: Tuple[str, ...], decide) -> set:
        ref = decide(base)
        target = max_flip_rate * ref.size

        def flips(active) -> int:
            qcfg = base.replace(quantize=True,
                                quant_scales=full.subset(tuple(active)))
            return int((decide(qcfg) != ref).sum())

        dropped: set = set()
        active = [n for n in names if n in full.names()]
        while active and flips(active) > target:
            scored = [(n, flips([m for m in active if m != n]))
                      for n in active]
            drop, _ = min(scored, key=lambda kv: kv[1])
            active.remove(drop)
            dropped.add(drop)
        return dropped

    dropped: set = set()
    dropped |= _prune_stream(
        _stream_layers(paper_models.mlp_apply, pkt_params, pkt_x),
        lambda cfg: np.asarray(decisions.decide_binary(
            paper_models.mlp_apply(pkt_params, pkt_x, config=cfg))))
    for fn, fp, fx, real in flow_samples:
        if not real:  # zero-row fallback: no decisions to measure against
            continue
        dropped |= _prune_stream(
            _stream_layers(fn, fp, fx),
            lambda cfg, fn=fn, fp=fp, fx=fx: np.asarray(
                jnp.argmax(fn(fp, fx, config=cfg), axis=-1)))
    return full.subset(tuple(n for n in full.names() if n not in dropped))


def quant_divergence_report(scales: QuantScales, *, steps: int = 10,
                            traffic=None, flow_model: str = "cnn",
                            ) -> Tuple[str, dict]:
    """Quantized-vs-f32 differential on the seeded stream: drives two
    identically-seeded pipelines (one f32, one int8 under ``scales``) and
    reports the decision-flip counts — packet allow/deny and flow class —
    plus whether tracker state stayed bit-exact (it must: only engine
    outputs quantize).  Returns ``(report_text, metrics)``."""
    import jax
    import numpy as np

    from repro.data.traffic import TrafficGenerator
    from repro.models import paper_models
    from repro.runtime import runtime_overrides
    from repro.serving import OctopusPipeline, PipelineConfig

    tcfg = traffic if traffic is not None else _traffic_config()
    pkt_params = paper_models.init_paper_model("mlp", jax.random.PRNGKey(0))
    flow_params = paper_models.init_paper_model(flow_model, jax.random.PRNGKey(1))
    pcfg = PipelineConfig(batch_size=tcfg.batch_size, max_ready=8,
                          flow_model=flow_model, table_size=tcfg.table_size)
    with runtime_overrides(quantize=False):
        ref = OctopusPipeline(pkt_params, flow_params, pcfg)
    with runtime_overrides(quantize=True, quant_scales=scales):
        q = OctopusPipeline(pkt_params, flow_params, pcfg)

    gen_a, gen_b = TrafficGenerator(tcfg), TrafficGenerator(tcfg)
    pkt_flips = pkt_total = flow_flips = flow_total = 0
    state_exact = True
    for _ in range(steps):
        ba, bb = gen_a.next_batch(), gen_b.next_batch()
        oa, ob = ref.step(ba), q.step(bb)
        pkt_a, pkt_b = np.asarray(oa.pkt_actions), np.asarray(ob.pkt_actions)
        pkt_flips += int((pkt_a != pkt_b).sum())
        pkt_total += pkt_a.size
        mask = np.asarray(oa.drained.mask)
        cls_a, cls_b = np.asarray(oa.flow_cls), np.asarray(ob.flow_cls)
        flow_flips += int((cls_a[mask] != cls_b[mask]).sum())
        flow_total += int(mask.sum())
        for la, lb in zip(jax.tree_util.tree_leaves(ref.state),
                          jax.tree_util.tree_leaves(q.state)):
            if not np.array_equal(np.asarray(la), np.asarray(lb)):
                state_exact = False
    metrics = {
        "pkt_flips": pkt_flips, "pkt_total": pkt_total,
        "flow_flips": flow_flips, "flow_total": flow_total,
        "pkt_flip_rate": pkt_flips / max(pkt_total, 1),
        "flow_flip_rate": flow_flips / max(flow_total, 1),
        "tracker_bit_exact": state_exact,
    }
    text = (
        f"int8-vs-f32 differential ({flow_model}, {steps} microbatches, "
        f"scales {scales.fingerprint}):\n"
        f"  decision flips: pkt {pkt_flips}/{pkt_total} "
        f"({100 * metrics['pkt_flip_rate']:.2f}%), "
        f"flow {flow_flips}/{flow_total} "
        f"({100 * metrics['flow_flip_rate']:.2f}%)\n"
        f"  tracker state bit-exact: {'yes' if state_exact else 'NO'}")
    return text, metrics


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="calibrate tau/vpe_max_elems from measured crossover points")
    ap.add_argument("--out", default=None,
                    help="artifact path (default: the backend-keyed cache path, "
                         f"{autotune.cache_path()})")
    ap.add_argument("--smoke", action="store_true",
                    help="8-point grid, 2 timing iters (CI / smoke tests)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations per shape per path (default 5; 2 with --smoke)")
    ap.add_argument("--flows", type=int, default=1000,
                    help="tracked flows for the paper-model divergence report")
    ap.add_argument("--verbose", action="store_true",
                    help="print the full calibrated RoutePlan per model")
    ap.add_argument("--quant", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="also fit int8 per-layer scales from a traffic "
                         "sample and report decision flips (--no-quant skips)")
    ap.add_argument("--quant-steps", type=int, default=None,
                    help="traffic microbatches for scale fitting "
                         "(default 16; 6 with --smoke)")
    args = ap.parse_args(argv)

    fp = platform.fingerprint()
    print(f"[calibrate] platform: {platform.fingerprint_id(fp)} "
          f"(interpret_default={platform.interpret_default()})")
    iters = args.iters if args.iters is not None else (2 if args.smoke else 5)
    grid = autotune.default_grid(smoke=args.smoke)
    print(f"[calibrate] sweeping {len(grid)} (m,k,n) shapes x 2 engine paths "
          f"({iters} iters each)...")
    calib = autotune.calibrate(grid, iters=iters)
    if args.quant:
        q_steps = args.quant_steps if args.quant_steps is not None else (
            6 if args.smoke else 16)
        flow_models = ("cnn",) if args.smoke else ("cnn", "transformer")
        print(f"[calibrate] fitting int8 scales from {q_steps} traffic "
              f"microbatches ({', '.join(flow_models)})...")
        scales = calibrate_quant_scales(steps=q_steps, flow_models=flow_models)
        calib = dataclasses.replace(calib, quant_scales=scales)
    path = autotune.save_calibration(calib, args.out)

    n_vpe = sum(1 for t in calib.timings if t.vpe_wins)
    print(f"[calibrate] vpe won {n_vpe}/{len(calib.timings)} shapes")
    print(f"[calibrate] analytic: tau={DEFAULT_RUNTIME.tau} "
          f"vpe_max_elems={DEFAULT_RUNTIME.vpe_max_elems}")
    print(f"[calibrate] measured: tau={calib.tau:.4f} "
          f"vpe_max_elems={calib.vpe_max_elems}")
    print(f"[calibrate] artifact: {path}")
    print()
    print("placement divergence (analytic -> calibrated):")
    print(divergence_report(calib.apply(RuntimeConfig()), flows=args.flows,
                            verbose=args.verbose))
    if args.quant and calib.quant_scales is not None:
        print(f"[calibrate] int8 scales: {calib.quant_scales.fingerprint} "
              f"({len(calib.quant_scales.entries)} layers)")
        q_steps = args.quant_steps if args.quant_steps is not None else (
            6 if args.smoke else 10)
        text, _ = quant_divergence_report(calib.quant_scales, steps=q_steps)
        print()
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
