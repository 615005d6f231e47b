"""Standard-normal private values over the configuration's per-node leaf
shapes (``values.leaves``), made on the device in one jitted call."""
from benchlib import weights


def make(cfg: dict, key, n_nodes: int):
    return weights.normal_values(cfg["values"]["leaves"], key, n_nodes)
