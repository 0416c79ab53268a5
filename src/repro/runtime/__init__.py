"""Unified Octopus runtime: one config, one placement plan, one API.

    from repro.runtime import RuntimeConfig, octopus_runtime, RoutePlan

    with octopus_runtime(RuntimeConfig(policy="collaborative", tau=0.35)):
        y = router.matmul(x, w)                       # ambient config
    plan = RoutePlan.trace(fn, abstract_x)            # shared placement truth
    print(plan.explain())

Self-calibration (measured arype/vpe crossover, see ``repro.runtime.autotune``):

    cfg = RuntimeConfig.calibrated()                  # backend-keyed cache
    with octopus_runtime(load_calibration(path)):     # or apply an artifact
        ...
"""
from repro.runtime import platform
from repro.runtime.autotune import (
    Calibration,
    ShapeTiming,
    calibrate,
    fit_crossover,
    load_calibration,
    measure_crossover,
    save_calibration,
)
from repro.runtime.config import (
    POLICIES,
    RuntimeConfig,
    current_runtime,
    octopus_runtime,
    resolve_config,
    runtime_overrides,
)
from repro.runtime.plan import PlannedMatmul, RoutePlan
from repro.runtime.quant import QuantScales, record_scales


def __getattr__(name: str):
    # DEFAULT_RUNTIME is lazy: constructing it probes the JAX backend, which
    # must not happen as an import side effect (see repro.runtime.config).
    if name == "DEFAULT_RUNTIME":
        from repro.runtime import config

        return config.DEFAULT_RUNTIME
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
from repro.runtime.routing import (
    Route,
    RouteRecord,
    lane_scope,
    mxu_utilization,
    name_scope,
    record_routes,
    route_matmul,
    systolic_utilization,
)
from repro.runtime.trace import SpanTotal, span

__all__ = [
    "Calibration",
    "DEFAULT_RUNTIME",
    "POLICIES",
    "PlannedMatmul",
    "QuantScales",
    "Route",
    "RouteRecord",
    "RoutePlan",
    "RuntimeConfig",
    "ShapeTiming",
    "SpanTotal",
    "calibrate",
    "current_runtime",
    "fit_crossover",
    "lane_scope",
    "load_calibration",
    "measure_crossover",
    "mxu_utilization",
    "name_scope",
    "octopus_runtime",
    "platform",
    "record_routes",
    "record_scales",
    "resolve_config",
    "route_matmul",
    "runtime_overrides",
    "save_calibration",
    "span",
    "systolic_utilization",
]
