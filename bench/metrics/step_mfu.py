"""Model FLOPs the window completed (the packet model on every judged
packet, the flow model on every classified flow) per second, over the
chips' bf16 peak.  A float32 matmul runs at DEFAULT precision (one bf16
pass) on the TPU, so the bf16 peak is the denominator."""
from bench import work


def read(run):
    peak = run["peaks"]
    if not peak or not run["packets_in_window"]:
        return None
    cfg = run["config"]
    flops = (run["packets_in_window"] * work.mlp_flops(cfg["packet_model"]["dims"])
             + run["flows_in_window"] * work.flow_flops(cfg["flow_model"]))
    return 100.0 * flops / run["seconds"] / (run["chips"] * peak["bf16_flops"])
