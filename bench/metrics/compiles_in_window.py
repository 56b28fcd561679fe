"""Programs compiled or loaded inside the window (JAX monitoring events);
each one stalls the round that needs it. Should be 0."""


def read(obs):
    return float(obs.compiles)
