"""The comparison that decides ``correct``: served tokens against the reference.

Once the window has closed and the program's state is freed, a sample of the
requests each resident finished, drawn from the seed and holding its longest,
is run through the plain float32 reference of its architecture
(``bench/reference/<arch>.py``, found by ``bench/arch.py``) with the prompt
and the served tokens. At each served position the number read is the gap by
which the served token's reference logit lies below the reference's
best logit there; for greedy decoding it is 0 where the program agrees, and
small where a near tie flipped on rounding. The number compared, per resident,
is the widest such gap; its limit is the configuration's ``limits.served_gap``.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import arch

SAMPLE_TOKENS = 256  # served tokens to compare per resident, at the least


def sample(records: List, seed: int, want: int = SAMPLE_TOKENS) -> List:
    """The longest finished request (prompt and answer), then others drawn
    from the seed, until ``want`` served tokens are in the sample."""
    done = sorted((r for r in records if r.served is not None), key=lambda r: r.spec.uid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.served), -r.spec.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    picked, n = [longest], len(longest.served)
    for i in order:
        if n >= want:
            break
        picked.append(rest[i])
        n += len(rest[i].served)
    return picked


def _sequences(picked):
    seqs, rows, toks = [], [], []
    for r in picked:
        p, s = np.asarray(r.prompt, np.int32), np.asarray(r.served, np.int32)
        seqs.append(np.concatenate([p, s[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) + len(s) - 1))
        toks.append(s)
    return seqs, rows, toks


def reference(conf: dict, seed: int, quant: Optional[str] = None):
    return arch.parts(conf).reference(conf["model"], seed, quant)


def served_gaps(conf: dict, seed: int, picked: List) -> np.ndarray:
    """The gap of every served token in ``picked`` (an out-of-vocab token
    reads as infinitely far)."""
    seqs, rows, toks = _sequences(picked)
    vocab = conf["model"]["vocab_size"]
    if any(t.min() < 0 or t.max() >= vocab for t in toks):
        return np.array([np.inf])
    res = reference(conf, seed).score(seqs, rows, toks)
    return np.concatenate([best - at for best, at, _ in res])


def control_gaps(conf: dict, seed: int, picked: List, quant: str) -> np.ndarray:
    """The control: at the same positions of the same sequences, the gap of
    the token that the reference computed in ``quant`` puts first."""
    seqs, rows, toks = _sequences(picked)
    low = reference(conf, seed, quant).score(seqs, rows, toks)
    res = reference(conf, seed).score(seqs, rows, [am for _, _, am in low])
    return np.concatenate([best - at for best, at, _ in res])


def verdict(confs: Dict[str, dict], seed: int, records: List,
            control: Optional[str] = None) -> tuple:
    """(correct, checks): per resident, the widest served gap beside its
    limit, and how many tokens it rests on. With ``control`` (a precision),
    the tokens that the reference at that precision puts first take the
    served tokens' place, at the same positions: the control, which has to
    come out not correct."""
    checks, ok = {}, True
    for name, conf in confs.items():
        picked = sample([r for r in records if r.spec.model == name], seed)
        if not picked:
            checks[f"finished.{name}"] = {"value": 0, "limit": 1}
            ok = False
            continue
        gaps = (served_gaps(conf, seed, picked) if control is None
                else control_gaps(conf, seed, picked, control))
        limit = conf["limits"]["served_gap"]
        widest = float(gaps.max())
        checks[f"gap.{name}"] = {"value": widest, "limit": limit,
                                 "tokens": int(gaps.size), "requests": len(picked)}
        ok = ok and widest <= limit
    return ok, checks
