"""Work the algorithm requires, from shapes alone.

These count what any implementation must do, not what the program happens
to emit, so a later change that fuses or removes a kernel leaves them
valid. See the metric readers under ``metrics/`` for how each is used.
"""
from __future__ import annotations

F32 = 4


def xlstm_leaf_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Per-node parameter shapes of the xLSTM stack, keyed by path.

    The tree is embed -> ``n_units`` x [``mlstm_per_unit`` mLSTM blocks,
    one sLSTM block] -> final norm, with the head tied to the embedding.
    Paths are ``/``-joined dict keys; leaves stack units (and the mLSTM
    blocks of a unit) on leading axes.
    """
    d, v, h = model["d_model"], model["vocab_size"], model["n_heads"]
    u, m = model["n_units"], model["mlstm_per_unit"]
    di = int(d * model["proj_factor"])
    shapes = {
        "embed": (v, d),
        "final_ln/scale": (d,),
        "group_0/mlstm/cell/b_if": (u, m, 2 * h),
        "group_0/mlstm/cell/w_down": (u, m, di, d),
        "group_0/mlstm/cell/w_if": (u, m, di, 2 * h),
        "group_0/mlstm/cell/w_k": (u, m, di, di),
        "group_0/mlstm/cell/w_o": (u, m, d, di),
        "group_0/mlstm/cell/w_q": (u, m, di, di),
        "group_0/mlstm/cell/w_up": (u, m, d, di),
        "group_0/mlstm/cell/w_v": (u, m, di, di),
        "group_0/mlstm/ln/scale": (u, m, d),
        "group_0/slstm/cell/b": (u, 4 * d),
        "group_0/slstm/cell/r": (u, d, 4 * d),
        "group_0/slstm/cell/w": (u, d, 4 * d),
        "group_0/slstm/ln/scale": (u, d),
    }
    return dict(sorted(shapes.items()))


def size(shape: tuple[int, ...]) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def train_flops_per_step(n_params: int, tokens_per_node: int,
                         n_nodes: int) -> float:
    """Required FLOPs of one PartPSP step over all nodes: 10 * P * T each.

    Pass 1 (Eq. 5) needs a forward pass, the activation gradients and the
    weight gradients of the *local* parameters; pass 2 (Eq. 6) a forward
    pass, the activation gradients and the weight gradients of the *shared*
    parameters. That is 2 forwards (2 * 2PT), 2 activation-gradient passes
    (2 * 2PT) and one weight gradient per parameter (2PT): 10PT per node.
    P counts the tied embedding once (for the head matmul; the gather costs
    no FLOPs). Recurrent cell updates and rematerialization are not counted.
    """
    return 10.0 * n_params * tokens_per_node * n_nodes


def perturb_bytes_per_round(n_nodes: int, d_s: int, perturbs: bool) -> float:
    """Least HBM bytes of the perturb + noise step: read s, read eps (where
    the traffic perturbs), write s'. Noise drawn on the chip moves none."""
    return F32 * n_nodes * d_s * (3 if perturbs else 2)


def round_bytes_per_round(n_nodes: int, d_pad: int) -> float:
    """Least HBM bytes of one whole DPPS round: the packed state read once
    and written once."""
    return 2 * F32 * n_nodes * d_pad
