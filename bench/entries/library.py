"""The library entry: ``repro.core.multilevel.multigila_layout`` called in
the client's own thread, edge array on the host in, positions on the host
out. A configuration names it as ``"entry": "library"``."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib.drive import Answer


class System:
    def __init__(self, conf: dict):
        from repro.core import LayoutConfig, multigila_layout
        self._layout = multigila_layout
        self._cfg = LayoutConfig(**conf["layout"])

    def encode(self, edges: np.ndarray, n: int, seed: int):
        return edges, n, seed

    def call(self, req) -> Answer:
        edges, n, seed = req
        pos, stats = self._layout(edges, n, dataclasses.replace(self._cfg,
                                                                seed=seed))
        return Answer(True, np.asarray(pos),
                      note=f"levels={stats.levels}")

    def positions(self, ans: Answer) -> np.ndarray:
        return ans.body

    def close(self) -> None:
        pass
