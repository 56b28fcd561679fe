"""An architecture's parts, found by the name its configuration gives.

A configuration's ``reference`` key names its architecture, and three modules
of that name describe it; a new architecture is these three new files:

- ``bench/weights/<arch>.py``: ``served_params(seed, m)``, the seeded weights
  in the program's parameter layout, made on the device in one jitted call
  (and whatever the reference needs to make the same numbers layer by layer);
- ``bench/reference/<arch>.py``: ``Reference(m, seed, quant)``, the plain
  float32 reference (``quant`` its lower-precision control), whose
  ``score(seqs, rows, tokens)`` ``bench/check.py`` compares against;
- ``bench/costs/<arch>.py``: ``decode_step(m, positions, counters)`` and
  ``prefill(m, lengths, counters)``, each ``(flops, bytes)`` that the step
  needs, from the shapes in ``m`` (the configuration's ``model`` block) and,
  where the work depends on what the program chose (the experts a router
  picked), from ``counters``: what the program counted for this resident
  over the window, the ledger's keys ``<resident>.<counter>`` under
  ``<counter>`` (``Observed.resident_counters``).
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from types import ModuleType


@dataclass(frozen=True)
class Parts:
    weights: ModuleType
    reference: type
    costs: ModuleType


def parts(conf: dict) -> Parts:
    name = conf["reference"]

    def part(kind: str) -> ModuleType:
        return importlib.import_module(f"bench.{kind}.{name}")

    return Parts(weights=part("weights"), reference=part("reference").Reference,
                 costs=part("costs"))
