"""Host time spent planning per round: step and prefill plans, the drift
check and the admission decision (their union, so nesting counts once)."""


def read(obs):
    return obs.per_round("_plan_for", "_prefill_plan_for", "_drift_event", "admission.decide")
