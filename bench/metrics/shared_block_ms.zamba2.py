"""Device milliseconds per training step in Zamba2's tied transformer
block: ``model_attn`` (the concat, its norm and the attention) plus
``model_mlp`` (the MLP with its adapter, and the hybrid layer's linear),
both gradient passes, from the kind's ``view["model_phase_s"]``. Only the
shared block has attention or an MLP in Zamba2. Layer: model. Moves
train_tokens_per_s."""

PHASES = ("model_attn", "model_mlp")


def read(view):
    phases = view.get("model_phase_s") or {}
    if not any(p in phases for p in PHASES) or not view["steps"]:
        return None
    return 1e3 * sum(phases.get(p, 0.0) for p in PHASES) / view["steps"]
