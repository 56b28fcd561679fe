"""Host time per engine round inside the planner's cold solves (the
program's ``repro.plan.solve`` spans, their union, over the number of
``repro.engine.round`` spans in the window). None where the program has no
such spans."""

from bench import trace_reduce as tr


def _started_in(obs, name):
    return [tuple(iv) for iv in obs.trace["spans"].get(name, []) if obs.window[0] <= iv[0] < obs.window[1]]


def read(obs):
    rounds = _started_in(obs, "repro.engine.round")
    if not rounds:
        return None
    solves = _started_in(obs, "repro.plan.solve")
    return 1e3 * tr.total(tr.union(tr.clip(solves, obs.window))) / len(rounds)
