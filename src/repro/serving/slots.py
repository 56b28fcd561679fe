"""Slot-pool state for continuous batching: requests, responses, the
fixed-size cache-row allocator and the per-model pool.

Split out of the engine monolith; ``repro.serving.engine`` re-exports every
name here so pre-refactor import paths keep working.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core.telemetry import EnergyBreakdown


@dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    enc_inputs: Optional[np.ndarray] = None
    t_submit: float = 0.0  # stamped by ServingEngine.submit
    # graceful-degradation fields (repro.serving.robustness): priority
    # orders battery-critical load shedding (lower sheds first); a deadline
    # turns into timeout -> bounded requeue-with-backoff -> explicit error
    priority: int = 0
    deadline_s: Optional[float] = None  # relative to t_submit; None = none
    retries: int = 0  # deadline requeues consumed so far


@dataclass
class Response:
    uid: int
    tokens: np.ndarray
    latency_s: float
    energy_j_pred: float
    # set when the request was rejected instead of served (e.g. oversized
    # prompt): the serving loop keeps draining, it never crashes mid-_admit
    error: Optional[str] = None
    # per-rail split of energy_j_pred (attribution from the partition plan's
    # physics fractions); None on the scheduler-less / bucketed-NaN paths
    rails: Optional[EnergyBreakdown] = None
    # submit to first token on the host, after the token reached it
    # (continuous mode; None on the bucketed path and for rejections)
    ttft_s: Optional[float] = None
    model: str = ""  # the worker that answered


class SlotAllocator:
    """Fixed pool of cache rows for continuous batching. O(1) alloc/free,
    LIFO reuse so the most-recently-retired row (hottest in cache) is handed
    out first. Double-free and foreign-slot frees raise."""

    def __init__(self, n_slots: int):
        if n_slots <= 0:
            raise ValueError(f"n_slots must be positive, got {n_slots}")
        self.n_slots = n_slots
        self._free = list(range(n_slots - 1, -1, -1))
        self._in_use: set = set()

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return len(self._in_use)

    def alloc(self) -> Optional[int]:
        """Returns a free slot index, or None when the pool is full."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._in_use.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        self._in_use.remove(slot)
        self._free.append(slot)


@dataclass
class _ActiveSeq:
    """A request resident in a cache slot."""
    req: Request
    slot: int
    pos: int  # next cache write position (prompt_len + generated so far)
    model: str = ""  # owning worker (stamped at admission; telemetry key)
    tokens: List[int] = field(default_factory=list)
    t_first: Optional[float] = None  # engine clock when the first token landed
    # the ONE energy tally: per-rail attribution (plan-derived fractions)
    # whose total_j accumulates the charged step/prefill energies
    rails: EnergyBreakdown = field(default_factory=EnergyBreakdown)
    # seed-derived per-request sampling stream (None on the greedy path):
    # token i draws from fold_in(rng, i), so sampled decode is reproducible
    # under ANY admission order / slot placement / co-resident set
    rng: Optional[jax.Array] = None
    # speculative decode (repro.serving.speculative; inert without a draft):
    # draft_pos is the draft cache's frontier — the next position the draft
    # worker writes (== how much of the committed sequence it has consumed);
    # spec_hist is the sliding (accepted, offered) window behind the
    # per-slot adaptive k
    draft_pos: int = 0
    spec_hist: List = field(default_factory=list)

    @property
    def energy_j(self) -> float:
        return self.rails.total_j


class _SlotPool:
    """Per-model continuous-batching state: the slot cache + allocator plus
    the dense (max_slots,) token/position arrays fed to the ragged decode."""

    def __init__(self, worker, max_slots: int):
        self.cache = worker.init_pool(max_slots)
        # mesh-aware pools record their cache region's NamedSharding tree
        # (batch rows -> data axes, kv-heads -> model with the KV-sequence
        # fallback; see repro.sharding.partition_specs.cache_spec) so tests
        # and benches can introspect placement; None on the single-device
        # path, which allocates exactly as before
        self.cache_shardings = (
            worker._cache_shardings.get((max_slots, worker.max_enc_len))
            if worker.mesh is not None else None)
        self.alloc = SlotAllocator(max_slots)
        self.active: Dict[int, _ActiveSeq] = {}
        self.tokens = np.zeros((max_slots, 1), np.int32)
        self.pos = np.zeros(max_slots, np.int32)
        # per-slot valid encoder length (enc-dec models): decode masks each
        # row's cross-attention to its own encoder region
        self.enc_len = np.zeros(max_slots, np.int32)
