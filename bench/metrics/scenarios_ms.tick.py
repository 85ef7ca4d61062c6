"""Fault masks, the forecast ensemble and the warm start ahead of the
planner call, ms per eager tick: the runtime's ``scenarios`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "scenarios" not in spans:
        return None
    return 1e3 * sum(spans["scenarios"]) / n
