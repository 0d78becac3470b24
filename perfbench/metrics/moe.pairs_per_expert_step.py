"""Tokens a held expert computes a step: `moe_pairs{held=here}` over
`moe_expert_steps` (steps x expert layers x experts held), the run's
steps together. How near a held expert's load is to the deployment's,
where the chips of a group each bring their own requests."""

HERE, EXPERT_STEPS = "moe_pairs{held=here}", "moe_expert_steps"


def read(run):
    steps = run.counters.get(EXPERT_STEPS, 0)
    if HERE not in run.counters or steps <= 0:
        return None
    return run.counters[HERE] / steps
