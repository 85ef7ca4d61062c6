"""From the planner's outputs on the device to the priced what-if
result, ms per eager tick: the runtime's ``plan.fetch`` (copies to the
host), ``plan.decode`` (plan objects) and ``plan.price`` (cross-ensemble
pricing) spans."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "plan.fetch" not in spans:
        return None
    return 1e3 * (sum(spans["plan.fetch"]) + sum(spans.get("plan.decode", ()))
                  + sum(spans.get("plan.price", ()))) / n
