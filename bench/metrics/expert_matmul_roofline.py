"""Share of its roofline that the routed experts' grouped matmuls reach in
the decode programs: for each decode call in the window of a resident whose
architecture counts its experts apart (``expert_decode`` in its costs
module), the least time of those matmuls (the weights of the experts the
window's steps touched, by the program's counters, at peak HBM bandwidth,
or 2 x 3 x d x F per assignment at peak FLOP/s, whichever is longer),
summed, over the device time of the ops that the trace names as those
matmuls inside the ``_decode_impl`` programs. On a TPU v5e XLA names each
one ``ragged-dot-none.<n>`` (the grouped matmul ``jax.lax.ragged_dot``
lowers to; ``ragged-dot-metadata`` ops only prepare its group offsets)."""

import bisect

from bench import trace_reduce as tr

NAME = "ragged-dot-none"


def read(obs):
    least = 0.0
    for model, positions in obs.decode_calls:
        costs = obs.costs[model]
        if not hasattr(costs, "expert_decode"):
            continue
        flops, nbytes = costs.expert_decode(obs.models[model], len(positions),
                                            obs.resident_counters(model))
        least += max(nbytes / obs.peaks["hbm_bytes_per_s"], flops / obs.peaks["bf16_flops"])
    device_s = 0.0
    for d in obs.trace["devices"]:
        mods = sorted((s, e) for name, s, e in d["modules"] if "_decode_impl" in name)
        starts = [s for s, _ in mods]
        inside = []
        for name, s, e in d["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if NAME in name and i >= 0 and e <= mods[i][1]:
                inside.append((s, e))
        device_s += tr.total(tr.union(tr.clip(inside, obs.window)))
    if not least or device_s <= 0:
        return None
    return 100.0 * least / device_s
