"""Model FLOP/s utilization of the window: the operations that the prompts
prefilled and tokens decoded inside it need (each resident's architecture's
costs module), over the window's seconds times chips times peak bf16 FLOP/s."""


def read(obs):
    flops = sum(obs.prefill(m, [n])[0] for m, n in obs.prefills)
    flops += sum(obs.decode_step(m, [p])[0] for m, p in obs.decoded)
    if not flops:
        return None
    return 100.0 * flops / (obs.window_s * obs.chips * obs.peaks["bf16_flops"])
