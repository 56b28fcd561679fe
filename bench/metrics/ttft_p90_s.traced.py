"""``ttft_p90_s`` as the end-to-end reader defines it (``bench/end_to_end``),
read in the traced run: the 90th percentile of time to first token over
every request due in the window, on the host clock. None where the run hands
no requests."""

from bench import served as served_mod


def read(obs):
    if obs.served is None or not obs.served.records:
        return None
    return served_mod.read("ttft_p90_s", obs.served)
