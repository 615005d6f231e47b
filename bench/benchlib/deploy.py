"""A configuration's deployment as the program takes it, and as the plain
reference takes it.

The topology is any family the program's ``make_topology`` knows, named by
the configuration's ``topology.kind`` with the rest of that object as its
parameters; the reference builds the same graph's weights from its own
``graphs/<kind>.py``. The privacy object passes through as given.
"""
from __future__ import annotations

import numpy as np

from benchlib import manifest


def _topo_params(cfg: dict) -> dict:
    return {k: v for k, v in cfg["topology"].items() if k != "kind"}


def topology(cfg: dict):
    """The program's topology for the configuration."""
    from repro.api import make_topology

    return make_topology(cfg["topology"]["kind"], cfg["nodes"],
                         **_topo_params(cfg))


def ref_weights(cell) -> np.ndarray:
    """The reference's (N, N) column-stochastic mixing matrix, from
    ``graphs/<kind>.py`` (``weights(n_nodes, **params)``)."""
    cfg = cell.config
    graph = manifest.load_module(cell.bench_dir, "graphs",
                                 cfg["topology"]["kind"])
    return graph.weights(cfg["nodes"], **_topo_params(cfg))


PRIVACY_KEYS = ("b", "gamma_n", "noise", "c_prime", "lam")


def privacy(cfg: dict):
    from repro.api import PrivacySpec

    p = cfg["privacy"]
    return PrivacySpec(**{k: p[k] for k in PRIVACY_KEYS if k in p})
