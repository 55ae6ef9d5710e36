"""The benchmark's one traffic generator: a deployment's base graph from
the seed, then one epoch of sender re-attestations after another.

The base graph is drawn by the module of the configuration's
``graph.kind``, ``benchmark/graphs/<kind>.py``, found by name: a new
deployment's shape is a new file there.  The churn is copied from the
program (``protocol_tpu/models/churn.py``) so that no later change to
the program can move the yardstick.  Every parameter comes from a
configuration file (``benchmark/configs``) or a traffic file
(``benchmark/traffic/*.json``); a new mix is a new data file.

A re-attestation is the protocol's unit of churn: a sender replaces its
whole out-row with a row of the graph's rounded mean out-degree, to
targets drawn uniformly over all ids.  The knobs a traffic file sets:

- ``cohort``: ``recency`` (ids exponential toward the newest ids, the
  shape of ``models/churn.py``) or ``uniform`` (without replacement);
- ``senders`` (a fixed count per epoch) or ``edge_fraction`` (the count
  that rewrites that share of the edges at the mean out-degree, as
  ``churn_cohort_dims`` computes it);
- ``weight_low`` / ``weight_high``: new weights, integers in
  ``[low, high)``.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from benchmark.modules import load_module


@dataclass
class Graph:
    """COO trust graph: ``src`` scored ``dst`` with ``weight``;
    ``pre_trusted`` flags the pre-trust set."""

    n: int
    src: np.ndarray  # int32 (E,)
    dst: np.ndarray  # int32 (E,)
    weight: np.ndarray  # float32 (E,)
    pre_trusted: np.ndarray  # bool (n,)

    @property
    def nnz(self) -> int:
        return int(self.src.shape[0])


#: One module per graph kind, ``<kind>.py``, each with ``draw(n, nnz, *,
#: seed, **params) -> Graph``.
GRAPH_KINDS = Path(__file__).resolve().parents[1] / "graphs"


def base_graph(config: dict, seed: int, kinds: Path = GRAPH_KINDS) -> Graph:
    """The configuration's deployment graph, drawn by the module of its
    ``graph.kind`` in ``kinds`` from ``seed``, or from the
    configuration's own ``seed`` where it fixes the graph; the other
    keys of ``graph`` are the module's parameters."""
    spec = dict(config["graph"])
    kind = spec.pop("kind")
    path = kinds / f"{kind}.py"
    if not path.is_file():
        known = sorted(p.stem for p in kinds.glob("*.py"))
        raise SystemExit(f"unknown graph kind {kind!r}; known: {known}")
    module = load_module(path, "benchmark_graph_")
    n, nnz = spec.pop("peers"), spec.pop("attestations")
    return module.draw(n, nnz, seed=spec.pop("seed", seed), **spec)


class Churn:
    """One epoch of re-attestations after another, from one generator
    seeded by the run's seed."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic = traffic
        self.rng = np.random.default_rng([seed, 1])

    def cohort_dims(self, g: Graph) -> tuple[int, int]:
        """``(senders, mean out-degree)`` for this graph."""
        avg_deg = max(g.nnz / g.n, 1.0)
        deg = max(1, int(round(avg_deg)))
        if "senders" in self.traffic:
            return int(self.traffic["senders"]), deg
        return max(1, int(round(self.traffic["edge_fraction"] * g.nnz / avg_deg))), deg

    def step(self, g: Graph) -> tuple[np.ndarray, Graph]:
        """``(rows, next graph)``: the re-attesting sender ids (sorted,
        unique) and the graph after their rewrites."""
        t, rng = self.traffic, self.rng
        cohort, deg = self.cohort_dims(g)
        if t["cohort"] == "recency":
            offs = rng.exponential(scale=max(g.n * 0.02, cohort), size=cohort).astype(
                np.int64
            )
            rows = np.unique(g.n - 1 - np.minimum(offs, g.n - 1))
        elif t["cohort"] == "uniform":
            rows = np.sort(rng.choice(g.n, size=cohort, replace=False))
        else:
            raise ValueError(f"unknown cohort {t['cohort']!r}")
        is_row = np.zeros(g.n, bool)
        is_row[rows] = True
        ns = np.repeat(rows.astype(np.int32), deg)
        nd = rng.integers(0, g.n, ns.shape[0]).astype(np.int32)
        while (bad := nd == ns).any():  # no self-edges
            nd[bad] = rng.integers(0, g.n, int(bad.sum()))
        nw = rng.integers(t["weight_low"], t["weight_high"], ns.shape[0]).astype(
            np.float32
        )
        keep = ~is_row[g.src]
        return rows, Graph(
            g.n,
            np.concatenate([g.src[keep], ns]),
            np.concatenate([g.dst[keep], nd]),
            np.concatenate([g.weight[keep], nw]),
            g.pre_trusted,
        )


class Ahead:
    """Assembles the next epochs' graphs on a thread while the current
    epoch runs (one waits in the queue, the next is being made), as
    ``node/pipeline.py`` overlaps the host stage with the device stage.  ``get`` returns ``(rows, graph,
    wait_s)``: how long the caller waited counts as generator
    lateness."""

    def __init__(self, churn: Churn, graph: Graph):
        self._churn = churn
        self._graph = graph
        self._out: queue.Queue = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="traffic", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        g = self._graph
        try:
            while not self._stop.is_set():
                rows, g = self._churn.step(g)
                item = (rows, g)
                while not self._stop.is_set():
                    try:
                        self._out.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # handed to the consumer, which raises it
            self._out.put(e)

    def ready(self) -> None:
        """Block until the next graph is assembled."""
        while self._out.empty() and self._thread.is_alive():
            time.sleep(0.01)

    def get(self) -> tuple[np.ndarray, Graph, float]:
        start = time.perf_counter()
        item = self._out.get()
        if isinstance(item, BaseException):
            raise item
        return item[0], item[1], time.perf_counter() - start

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            raise RuntimeError("traffic generator did not stop")
