"""The fused scan program, ms per replayed tick: each chunk's ``scan_s``,
which ends in ``block_until_ready`` (records carry ``replan_s = scan_s /
T``)."""


def read(inputs):
    per_tick = inputs.get("scan_s")
    if not per_tick:
        return None
    return 1e3 * sum(per_tick) / len(per_tick)
