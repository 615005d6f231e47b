"""Device milliseconds per training step under the model's ``model_mamba2``
scope (every Mamba2 layer, both gradient passes), from the kind's
``view["model_phase_s"]`` (benchlib.spans.model_phase_s). Layer: model.
Moves train_tokens_per_s."""


def read(view):
    phases = view.get("model_phase_s") or {}
    if "model_mamba2" not in phases or not view["steps"]:
        return None
    return 1e3 * phases["model_mamba2"] / view["steps"]
