"""The wait for the planner's outputs on the device, ms per eager tick:
the runtime's ``plan.wait`` span (``block_until_ready``)."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "plan.wait" not in spans:
        return None
    return 1e3 * sum(spans["plan.wait"]) / n
