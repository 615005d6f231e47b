"""Roofline share of the perturb + Laplace-noise work in a training step.

Least time = least bytes / HBM peak, with least bytes = 4 B per wire
element for reading s, reading eps and writing s' (benchlib.counts;
noise drawn on the chip moves none); device time = the dpps_perturb and
dpps_noise phases per step. Bandwidth bounds it: the step does O(1)
FLOPs per byte. Layer: kernels. Moves train_tokens_per_s."""
from benchlib.counts import perturb_bytes_per_round

PHASES = ("dpps_perturb", "dpps_noise")


def read(view):
    s, cfg = view["summary"], view["cell"].config
    t = sum(s.phase_s.get(p, 0.0) for p in PHASES)
    if t <= 0 or not view["steps"]:
        return None
    least = (perturb_bytes_per_round(cfg["nodes"], cfg["d_s"], True)
             / view["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (t / view["steps"])
