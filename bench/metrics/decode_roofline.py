"""Share of its roofline that the decode program reaches: the least time the
chip could take for each decode call in the window (the bytes it needs, the
weights and the KV of the active slots up to their positions, at peak HBM
bandwidth, or its operations at peak FLOP/s, whichever is longer, as the
resident's architecture counts them), over the device time of the
``_decode_impl`` programs in the trace."""

from bench import trace_reduce as tr


def read(obs):
    device_s, n = tr.module_seconds(obs.trace, obs.window, "_decode_impl")
    if not n or device_s <= 0:
        return None
    least = 0.0
    for model, positions in obs.decode_calls:
        flops, nbytes = obs.decode_step(model, positions)
        least += max(nbytes / obs.peaks["hbm_bytes_per_s"], flops / obs.peaks["bf16_flops"])
    return 100.0 * least / device_s
