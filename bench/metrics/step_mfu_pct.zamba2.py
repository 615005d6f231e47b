"""Whole training step's share of the chip's bf16 peak for the Zamba2
share: the FLOPs PartPSP requires per step, times the steps the traced
window completed, over the window and the peak. Layer: whole step. Moves
train_tokens_per_s.

Per node and step: 10 P T for the parameters (benchlib.counts.
train_flops_per_step: two forwards, two activation-gradient passes, one
weight gradient) plus the causal attention's score and value products,
which have no parameters: 2 S d_a a token forward (d_a = heads held x
head_dim, half the S x S pairs), twice that backward, in each of the two
gradient passes, 12 S d_a a token. The SSD scan's own products and
rematerialization are not counted."""
from benchlib.counts import train_flops_per_step


def read(view):
    cfg, tr = view["cell"].config, view["cell"].traffic
    if not view["steps"]:
        return None
    seq = cfg["context_length"]
    tokens = tr["per_node_batch"] * seq
    d_a = cfg["num_attention_heads"] * cfg["attention_head_dim"]
    hybrid = sum(t == "hybrid" for t in cfg["layers_block_type"])
    attn = 12.0 * seq * d_a * tokens * hybrid * cfg["nodes"]
    flops = (train_flops_per_step(cfg["n_params"], tokens, cfg["nodes"])
             + attn) * view["steps"]
    return 100.0 * flops / view["window_s"] / view["peaks"]["bf16_flops_per_s"]
