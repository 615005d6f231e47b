"""Device milliseconds per training step in PartPSP's gradient phases
(partpsp_local_grads + partpsp_shared_grads + partpsp_clip), from the
op_name join. Layer: core. Moves train_tokens_per_s."""

PHASES = ("partpsp_local_grads", "partpsp_shared_grads", "partpsp_clip")


def read(view):
    s = view["summary"]
    if not any(p in s.phase_s for p in PHASES) or not view["steps"]:
        return None
    return 1e3 * sum(s.phase_s.get(p, 0.0) for p in PHASES) / view["steps"]
