"""Graphs and keyword labels made from a seed: the benchmark's own copy.

A configuration file names its graph kind (``"kind"``); the kind is a
module ``bench/graphs/<kind>.py`` whose ``make(config, seed)`` returns a
:class:`Dataset`.  A new kind of graph is a new module there, found by
name.  Every array is made on the host with NumPy: the same
configuration and seed give the same graph, bit for bit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

import numpy as np

INF = 1e9
KINDS_DIR = Path(__file__).resolve().parent / "graphs"


@dataclasses.dataclass(frozen=True)
class Dataset:
    """Directed edges with their weights, and the node labels."""

    n_nodes: int
    src: np.ndarray      # int32[E]
    dst: np.ndarray      # int32[E]
    w: np.ndarray        # float32[E]
    tokens: np.ndarray   # int32[V, labels_per_node]


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def load_module(directory: Path, name: str, prefix: str):
    """The module ``directory/<name>.py``, loaded by its path."""
    path = directory / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in directory.glob("*.py"))
        raise KeyError(f"no {prefix} {name!r} in {directory}; known: {known}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{prefix}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_dataset(config: dict, seed: int) -> Dataset:
    """The graph of a configuration file, with its labels from ``seed``."""
    return load_module(KINDS_DIR, config["kind"], "graph").make(config, seed)


def postings(tokens: np.ndarray) -> dict[int, np.ndarray]:
    """token -> sorted distinct node ids carrying it."""
    v, l = tokens.shape
    flat = tokens.reshape(-1)
    nodes = np.repeat(np.arange(v, dtype=np.int32), l)
    order = np.lexsort((nodes, flat))
    flat, nodes = flat[order], nodes[order]
    bounds = np.flatnonzero(np.diff(flat)) + 1
    out = {}
    for s, t in zip(np.concatenate([[0], bounds]),
                    np.concatenate([bounds, [len(flat)]])):
        out[int(flat[s])] = np.unique(nodes[s:t])
    return out
