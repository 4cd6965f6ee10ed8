"""APSS → similarity graph → clusters: the paper's "similarity graph as a
computational kernel" application, end to end.

Builds an ε-neighborhood graph over a clustered corpus with the APSS core,
summarises it (edges, degrees) and reads clusters off it as its connected
components, scored against the classes the corpus was drawn from.

    PYTHONPATH=src python examples/similarity_graph.py
"""

import numpy as np

import jax.numpy as jnp

from repro.core.apss import apss_blocked, normalize_rows
from repro.core.graph import matches_to_coo


def make_clustered_corpus(n_per_class=64, n_classes=5, d=128, seed=0):
    """Gaussian clusters → rows with class structure the graph can reveal."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_classes, d)) * 2.0
    X, y = [], []
    for c in range(n_classes):
        X.append(centers[c] + rng.standard_normal((n_per_class, d)))
        y.append(np.full(n_per_class, c))
    X = np.concatenate(X).astype(np.float32)
    y = np.concatenate(y).astype(np.int32)
    perm = rng.permutation(len(X))
    return X[perm], y[perm]


def connected_components(n, rows, cols):
    """Component label (its smallest vertex) of every vertex: min-label
    propagation along the edges with pointer jumping."""
    labels = np.arange(n)
    while True:
        low = np.minimum(labels[rows], labels[cols])
        new = labels.copy()
        np.minimum.at(new, rows, low)
        np.minimum.at(new, cols, low)
        new = new[new]
        if (new == labels).all():
            return labels
        labels = new


def main() -> None:
    X, y = make_clustered_corpus()
    n = len(X)
    D = normalize_rows(jnp.asarray(X))

    # 1. similarity graph via the paper's algorithm
    t = 0.55
    matches = apss_blocked(D, t, k=32, block_rows=64)
    rows, cols, w = matches_to_coo(matches)
    degree = np.bincount(np.concatenate([rows, cols]), minlength=n)
    print(f"APSS: {len(rows)} edges at t={t} over {n} vectors; degree "
          f"min {degree.min()} median {int(np.median(degree))} "
          f"max {degree.max()}; weights in [{w.min():.3f}, {w.max():.3f}]")

    # 2. clusters = connected components of the graph
    labels = connected_components(n, rows, cols)
    comps = np.unique(labels)
    majority = sum(np.bincount(y[labels == c]).max() for c in comps)
    purity = majority / n
    print(f"{len(comps)} components over {len(np.unique(y))} classes; "
          f"purity {purity:.3f}")
    assert purity > 0.9, "the similarity graph should recover the classes"


if __name__ == "__main__":
    main()
