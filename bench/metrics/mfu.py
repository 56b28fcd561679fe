"""Model FLOP/s utilization of the window: the operations that the prompts
prefilled and tokens decoded inside it need (flops_bytes.py), over the
window's seconds times chips times peak bf16 FLOP/s."""

from bench import flops_bytes as fb


def read(obs):
    flops = sum(fb.prefill(obs.models[m], [n])[0] for m, n in obs.prefills)
    flops += sum(fb.decode_step(obs.models[m], [p])[0] for m, p in obs.decoded)
    if not flops:
        return None
    return 100.0 * flops / (obs.window_s * obs.chips * obs.peaks["bf16_flops"])
