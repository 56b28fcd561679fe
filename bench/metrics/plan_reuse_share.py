"""Share of the engine's plan lookups in the window that needed no solve:
100 x (1 - plan_cache_misses / plan_lookups), from the program's ledger
counters. None where the program does not count lookups."""


def read(obs):
    lookups = obs.counters.get("plan_lookups")
    if not lookups:
        return None
    return 100.0 * (1.0 - obs.counters.get("plan_cache_misses", 0) / lookups)
