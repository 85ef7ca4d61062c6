"""Lowering, ms per eager tick: the runtime's ``lower.rebuild`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "lower.rebuild" not in spans:
        return None
    return 1e3 * sum(spans["lower.rebuild"]) / n
