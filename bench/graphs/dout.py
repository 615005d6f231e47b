"""The d-Out graph (arXiv:2602.11544, Remark 2), built plainly for the
reference: node i sends to i, i+1, ..., i+d-1 (mod N) with weight 1/d
each, so W[(i + k) mod N, i] = 1/d."""
import numpy as np


def weights(n_nodes: int, degree: int) -> np.ndarray:
    w = np.zeros((n_nodes, n_nodes), np.float32)
    for i in range(n_nodes):
        for k in range(degree):
            w[(i + k) % n_nodes, i] += 1.0 / degree
    return w
