"""What the window served, as the end-to-end metric readers see it.

``bench/end_to_end/<name>.py`` each define ``read(served) -> float``. Times are
on the host clock (``time.time``), in seconds.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

import numpy as np

E2E_DIR = Path(__file__).resolve().parent / "end_to_end"


@dataclass
class Served:
    records: List  # harness.Record of every request submitted in the window
    t0: float
    t1: float
    setup_s: float

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def ttfts(self) -> np.ndarray:
        return np.array([(r.tokens[0] if r.tokens and r.error is None else self.t1) - r.due
                         for r in self.records])

    def gaps(self) -> np.ndarray:
        out = []
        for r in self.records:
            t = np.asarray([x for x in r.tokens if x <= self.t1])
            out.append(np.diff(t))
        return np.concatenate(out) if out else np.zeros(0)

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.records for x in r.tokens if self.t0 <= x <= self.t1)


def read(name: str, served: Served) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(f"bench_e2e_{name}", E2E_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(served)
