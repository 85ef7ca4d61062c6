"""The gates, the capacity-keeping commit, accounting and the capacity
check, ms per fleet tick: the fleet runtime's ``fleet.commit`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.commit" not in spans:
        return None
    return 1e3 * sum(spans["fleet.commit"]) / n
