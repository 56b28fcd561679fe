"""Traffic generator: one general generator for every mix file in ``bench/mixes``.

A mix is a JSON file of parameters (see ``bench/README.md``). Requests are made
in *blocks*: every block of every seed holds the same multiset of prompt
lengths, output lengths, models and inter-arrival gaps, and the seed only
shuffles them inside each block and fills the prompts with token ids. So two
seeds offer the same work in another order, and the spread between seeds is
the system's, not the generator's.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "mixes"
CELL = "@cell"  # stands, in a mix's list of residents, for the cell's own configuration


@dataclass
class RequestSpec:
    uid: int
    model: str
    prompt_len: int
    max_new: int
    due_s: Optional[float]  # offset from the window's start; None in a closed loop


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def residents(mix: dict, cell_config: str) -> List[str]:
    return [cell_config if r["config"] == CELL else r["config"] for r in mix["residents"]]


def _quantiles(n: int) -> np.ndarray:
    """Mid-points of ``n`` equal strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def _lognormal(q: np.ndarray, median: float, sigma: float) -> np.ndarray:
    nd = NormalDist()
    return median * np.exp(sigma * np.array([nd.inv_cdf(float(x)) for x in q]))


def prompt_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified prompt lengths. Either ``classes`` with ``weights``,
    or a lognormal (``median``, ``sigma``) rounded up to the next class (the
    largest class takes the tail)."""
    classes = np.asarray(spec["classes"])
    q = _quantiles(n)
    if "weights" in spec:
        cdf = np.cumsum(spec["weights"]) / np.sum(spec["weights"])
        return classes[np.minimum(np.searchsorted(cdf, q), len(classes) - 1)]
    raw = _lognormal(q, spec["median"], spec["sigma"])
    return classes[np.minimum(np.searchsorted(classes, raw), len(classes) - 1)]


def output_lengths(spec: dict, n: int) -> np.ndarray:
    raw = np.round(_lognormal(_quantiles(n), spec["median"], spec["sigma"]))
    return np.clip(raw, spec["min"], spec["max"]).astype(int)


def _split(shares, n: int) -> List[int]:
    """Whole counts of ``n`` in proportion to ``shares`` (largest remainder)."""
    exact = np.asarray(shares, float) / np.sum(shares) * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def make_block(mix: dict, names: List[str], rng: np.random.Generator) -> List[tuple]:
    """One block: (model, prompt_len, max_new, gap_s) tuples in seeded order.
    Each model gets its share of the block, with its own stratified lengths."""
    block = int(mix["block"])
    items = []
    for name, n in zip(names, _split([r["share"] for r in mix["residents"]], block)):
        plens = rng.permutation(prompt_lengths(mix["prompt"], n))
        outs = rng.permutation(output_lengths(mix["output"], n))
        items += [(name, int(p), int(o)) for p, o in zip(plens, outs)]
    items = [items[i] for i in rng.permutation(len(items))]
    gaps = [None] * block
    if mix["loop"] == "open":
        # Poisson arrivals: exponential gaps at stratified quantiles, scaled so
        # that every block spans exactly block / rate seconds
        g = -np.log1p(-_quantiles(block))
        gaps = rng.permutation(g / g.sum() * block / float(mix["rate_rps"])).tolist()
    return [it + (gap,) for it, gap in zip(items, gaps)]


class Schedule:
    """The run's requests, block after block. Open loop: ``due()`` lists every
    request due in the window. Closed loop: ``next()`` hands out the next
    request whenever a client needs one, without end."""

    def __init__(self, mix: dict, seed: int, cell_config: str):
        self.mix = mix
        self.names = residents(mix, cell_config)
        self._rng = np.random.default_rng(seed)
        self._pending: List[tuple] = []
        self._t = 0.0
        self._uid = 0

    def next(self) -> RequestSpec:
        if not self._pending:
            self._pending = make_block(self.mix, self.names, self._rng)
        model, plen, max_new, gap = self._pending.pop(0)
        due = None
        if gap is not None:
            self._t += gap
            due = self._t
        spec = RequestSpec(self._uid, model, plen, max_new, due)
        self._uid += 1
        return spec

    def due(self, seconds: float) -> List[RequestSpec]:
        out = []
        while True:
            spec = self.next()
            if spec.due_s >= seconds:
                return out
            out.append(spec)


def prompt_tokens(seed: int, uid: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of one prompt, from the seed and the request's uid."""
    return np.random.default_rng([seed, uid]).integers(1, vocab, length, dtype=np.int32)
