"""What a traced run observed, as the per-layer metric readers see it.

``bench/metrics/<name>.py`` each define ``read(obs) -> float | None``; a reader
that finds nothing to read returns None and its metric is left out. Times are
seconds: ``trace`` intervals on the profiler's clock, the rest on the host's.
The operations and bytes of a resident's step come from its architecture's
costs module (``bench/arch.py``), through ``decode_step`` and ``prefill``,
which hand it the counters the program keeps for that resident alone.
``served`` is the window's requests on the host clock, as the end-to-end
readers see them (``bench/served.py``).
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce as tr

METRIC_DIR = Path(__file__).resolve().parent / "metrics"


@dataclass
class Observed:
    trace: dict  # trace_reduce.from_xplane: spans "bench.*" and the program's "repro.*"
    window: Tuple[float, float]  # the measured window, on the trace's clock
    counters: Dict[str, int]  # engine and ledger counters, as deltas over the window
    compiles: int  # program compiles and cache loads inside the window
    decode_calls: List[tuple]  # (model, active positions) of each decode call in the window
    prefills: List[tuple]  # (model, prompt length) of each first token in the window
    decoded: List[tuple]  # (model, position) of each later token in the window
    models: Dict[str, dict]  # configuration "model" blocks by resident name
    costs: Dict[str, ModuleType]  # each resident's architecture's costs module
    peaks: dict  # the device's row of bench/peaks.json
    chips: int
    served: Optional[object] = None  # served.Served of the window, where a run hands it

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def rounds(self) -> int:
        return tr.span_seconds(self.trace, self.window, ["_serve_round"])[1]

    def span_s(self, *names: str) -> float:
        return tr.span_seconds(self.trace, self.window, names)[0]

    def per_round(self, *names: str) -> Optional[float]:
        """Milliseconds inside the named spans per engine round."""
        n = self.rounds
        return 1e3 * self.span_s(*names) / n if n else None

    def resident_counters(self, model: str) -> Dict[str, int]:
        """The window's counters that the program keeps for ``model`` alone,
        which its ledger keys ``<model>.<counter>``, under ``<counter>``."""
        head = model + "."
        return {k[len(head):]: v for k, v in self.counters.items() if k.startswith(head)}

    def decode_step(self, model: str, positions: Iterable[int]) -> Tuple[int, int]:
        """(flops, bytes) of one decode step of ``model`` over the active
        sequences at ``positions``."""
        return self.costs[model].decode_step(self.models[model], positions,
                                             self.resident_counters(model))

    def prefill(self, model: str, lengths: Iterable[int]) -> Tuple[int, int]:
        """(flops, bytes) of prefilling prompts of ``lengths`` on ``model``."""
        return self.costs[model].prefill(self.models[model], lengths,
                                         self.resident_counters(model))


def read(name: str, obs: Observed) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", METRIC_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)
