"""Requests per admission prefill batch over the window (engine counters)."""


def read(obs):
    n = obs.counters["prefill_batches"]
    return obs.counters["prefill_batch_requests"] / n if n else None
