"""Whole training step's share of the chip's bf16 peak: the FLOPs PartPSP
requires per step (10 P T per node, benchlib.counts.train_flops_per_step)
times the steps the traced window completed, over the window and the peak.
Layer: the whole step. Moves train_tokens_per_s."""
from benchlib.counts import train_flops_per_step


def read(view):
    cfg, tr = view["cell"].config, view["cell"].traffic
    if not view["steps"]:
        return None
    flops = train_flops_per_step(cfg["n_params"],
                                 tr["per_node_batch"] * cfg["context_length"],
                                 cfg["nodes"]) * view["steps"]
    return 100.0 * flops / view["window_s"] / view["peaks"]["bf16_flops_per_s"]
