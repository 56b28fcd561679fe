"""Mean host time of one ``ModelWorker.decode_pool`` call, which ends in a host sync."""

from bench import trace_reduce as tr


def read(obs):
    secs, n = tr.span_seconds(obs.trace, obs.window, ["decode_pool"])
    return 1e3 * secs / n if n else None
