"""Roofline share of the perturb + Laplace-noise work in a consensus
round: least bytes (read s, write s'; consensus perturbs nothing) over the
HBM peak, against the dpps_perturb + dpps_noise device time per round.
Layer: kernels. Moves consensus_rounds_per_s."""
from benchlib.counts import perturb_bytes_per_round

PHASES = ("dpps_perturb", "dpps_noise")


def read(view):
    s, cfg = view["summary"], view["cell"].config
    t = sum(s.phase_s.get(p, 0.0) for p in PHASES)
    if t <= 0 or not view["rounds"]:
        return None
    least = (perturb_bytes_per_round(cfg["nodes"], cfg["d_s"], False)
             / view["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (t / view["rounds"])
