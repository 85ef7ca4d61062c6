"""The jitted planner call until it returns (argument conversion,
host-to-device copies, enqueue), ms per eager tick: the runtime's
``plan.dispatch`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "plan.dispatch" not in spans:
        return None
    return 1e3 * sum(spans["plan.dispatch"]) / n
