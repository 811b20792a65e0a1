"""Seeded stand-in graphs at a published node and edge count.

The configuration names a dataset shape (nodes, directed edges,
features, classes, train share) and R-MAT parameters. The graph is a
function of the configuration alone, never of ``--seed``: every run of
a cell trains or serves on the same graph, so a seed changes weights
and traffic but not the work's shape. Nothing is downloaded.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class GraphData:
    """Host arrays of one node-classification graph.

    ``src``/``dst`` are the directed edges the model aggregates over
    (symmetrized, with one self loop per node), sorted by ``dst`` then
    ``src``;
    ``feats`` (n, d) float32, ``labels`` (n,) int32, ``train_mask`` (n,)
    bool."""
    n: int
    src: np.ndarray
    dst: np.ndarray
    feats: np.ndarray
    labels: np.ndarray
    train_mask: np.ndarray
    n_classes: int

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    def edge_keys(self) -> np.ndarray:
        """Sorted ``dst * n + src`` keys, for membership tests."""
        return np.sort(self.dst.astype(np.int64) * self.n + self.src)


def rmat_edges(n: int, n_edges: int, a: float, b: float, c: float,
               rng: np.random.Generator) -> tuple:
    """``n_edges`` distinct directed edges (no self loops) among ``n``
    nodes, drawn by R-MAT on the next power of two and kept where both
    ends are below ``n``; node ids then go through a random permutation
    so that degree does not follow the id."""
    scale = max(int(np.ceil(np.log2(n))), 1)
    d = 1.0 - a - b - c
    keys = np.empty(0, np.int64)
    while keys.size < n_edges:
        m = int((n_edges - keys.size) * 1.6) + 1024
        src = np.zeros(m, np.int64)
        dst = np.zeros(m, np.int64)
        for _ in range(scale):
            r = rng.random(m)
            sb = r >= a + b
            r2 = rng.random(m)
            db = np.where(sb, r2 >= c / (c + d), r2 >= a / (a + b))
            src = (src << 1) | sb
            dst = (dst << 1) | db
        ok = (src < n) & (dst < n) & (src != dst)
        keys = np.unique(np.concatenate([keys, src[ok] * n + dst[ok]]))
    keys = keys[rng.permutation(keys.size)[:n_edges]]
    perm = rng.permutation(n)
    return perm[keys // n], perm[keys % n]


def build(cfg: dict) -> GraphData:
    """The configuration's graph, features, labels and train split,
    from its own ``graph.seed``."""
    gc = cfg["graph"]
    n = int(gc["nodes"])
    rng = np.random.default_rng(int(gc["seed"]))
    src, dst = rmat_edges(n, int(gc["edges"]), *gc["rmat_abc"], rng)
    if gc.get("symmetric", True):
        keys = np.unique(np.concatenate([src * n + dst, dst * n + src]))
        src, dst = keys // n, keys % n
    if gc.get("self_loops", True):
        src = np.concatenate([src, np.arange(n)])
        dst = np.concatenate([dst, np.arange(n)])
    order = np.lexsort((src, dst))
    src, dst = src[order], dst[order]
    d = int(cfg["model"]["in_feats"])
    k = int(cfg["model"]["n_classes"])
    feats = rng.standard_normal((n, d), dtype=np.float32)
    proj = rng.standard_normal((d, k), dtype=np.float32)
    labels = np.argmax(feats @ proj, axis=1).astype(np.int32)
    train_mask = rng.random(n) < float(gc["train_share"])
    return GraphData(n=n, src=src.astype(np.int32), dst=dst.astype(np.int32),
                     feats=feats, labels=labels, train_mask=train_mask,
                     n_classes=k)
