"""Mean host time of one engine round (``ServingEngine._serve_round``)."""


def read(obs):
    return obs.per_round("_serve_round")
