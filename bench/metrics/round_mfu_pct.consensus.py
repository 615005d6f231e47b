"""Whole DPPS round's share of the chip's peak. The round does O(1) FLOPs
per byte, so the peak that bounds it is HBM bandwidth: least bytes per
round (the packed (N, d_pad) state read and written once,
benchlib.counts.round_bytes_per_round) times the rounds the traced window
completed, over the window and the HBM peak. Layer: the whole round.
Moves consensus_rounds_per_s."""
from benchlib.counts import round_bytes_per_round


def read(view):
    cfg = view["cell"].config
    if not view["rounds"]:
        return None
    moved = round_bytes_per_round(cfg["nodes"], cfg["d_pad"]) * view["rounds"]
    return 100.0 * moved / view["window_s"] / view["peaks"]["hbm_bytes_per_s"]
