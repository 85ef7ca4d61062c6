"""What-if planner, ms per eager tick: the runtime's ``plan.evaluate``
span (the batched plan of every forecast branch and their pricing)."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "plan.evaluate" not in spans:
        return None
    return 1e3 * sum(spans["plan.evaluate"]) / n
