"""Device milliseconds per training step in the DPPS round (every dpps_*
phase, the push-sum mix inside it, and the packed buffer's pack/unpack),
from the op_name join. Layer: core. Moves train_tokens_per_s."""

PHASES = ("dpps_perturb", "dpps_sensitivity", "dpps_noise", "dpps_gossip",
          "dpps_sync", "pushsum_mix", "engine_pack", "engine_unpack")


def read(view):
    s = view["summary"]
    if not any(p in s.phase_s for p in PHASES) or not view["steps"]:
        return None
    return 1e3 * sum(s.phase_s.get(p, 0.0) for p in PHASES) / view["steps"]
