"""Host time inside admission (``ServingEngine._admit``, prefill included) per round."""


def read(obs):
    return obs.per_round("_admit")
