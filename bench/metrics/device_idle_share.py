"""Share of the window in which no operation ran on the device (profiler trace)."""

from bench import trace_reduce as tr


def read(obs):
    busy = tr.busy_seconds(obs.trace, obs.window)
    return None if busy is None else 100.0 * (1.0 - busy / obs.window_s)
