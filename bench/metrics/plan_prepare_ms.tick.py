"""The planner call's host preparation (warm-start check, scenario
materialize, padding, constraint lowering), ms per eager tick: the
runtime's ``plan.prepare`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "plan.prepare" not in spans:
        return None
    return 1e3 * sum(spans["plan.prepare"]) / n
