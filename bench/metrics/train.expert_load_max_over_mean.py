"""train.expert_load_max_over_mean: the routed slots of the busiest held
expert over the mean held expert's, in each update (the counters every
update returns in ``StepMetrics.tower``: ``expert_slots_max`` and
``expert_slots_mean`` over every expert layer and held expert), averaged
over the window's updates. 1 is an even load."""


def read(d):
    return d.get("expert_load_max_over_mean")
