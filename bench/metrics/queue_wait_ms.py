"""Mean time a request waited in its queue before the admission that took
it, over the requests admitted in the window (the program's ledger counters
``queue_wait_us`` and ``admitted``). None where the program does not count
admissions."""


def read(obs):
    n = obs.counters.get("admitted")
    if not n:
        return None
    return obs.counters.get("queue_wait_us", 0) / n / 1e3
