"""Mean host time of one decode step (the program's ``repro.decode.step``
spans) not spent waiting for the device (``repro.decode.wait``) or planning
(``repro.plan.*``): dispatch, the simulator's accounting, the ledger and the
per-slot bookkeeping. None where the program has no such spans."""

import bisect

from bench import trace_reduce as tr


def read(obs):
    spans = obs.trace["spans"]
    steps = [tuple(iv) for iv in spans.get("repro.decode.step", []) if obs.window[0] <= iv[0] < obs.window[1]]
    if not steps:
        return None
    waits = tr.union([tuple(iv) for name, ivs in spans.items()
                      if name == "repro.decode.wait" or name.startswith("repro.plan.") for iv in ivs])
    ends = [e for _, e in waits]
    host = 0.0
    for s, e in steps:
        covered = 0.0
        for ws, we in waits[bisect.bisect_right(ends, s):]:
            if ws >= e:
                break
            covered += min(we, e) - max(ws, s)
        host += (e - s) - covered
    return 1e3 * host / len(steps)
