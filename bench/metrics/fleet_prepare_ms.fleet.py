"""``plan_many``'s host preparation (warm-start check, padding,
constraint lowering, grouping), ms per fleet tick: the
``fleet.prepare`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.prepare" not in spans:
        return None
    return 1e3 * sum(spans["fleet.prepare"]) / n
