"""Host staging ahead of the fused scan, ms per replayed tick: each
chunk's ``stage_s`` (its records carry ``constraint_s = stage_s / T``)."""


def read(inputs):
    per_tick = inputs.get("stage_s")
    if not per_tick:
        return None
    return 1e3 * sum(per_tick) / len(per_tick)
