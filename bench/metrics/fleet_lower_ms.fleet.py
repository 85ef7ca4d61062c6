"""Per-tenant lowering, fault masking, warm start and the fleet problem's
build, ms per fleet tick: the fleet runtime's ``fleet.lower`` span (the
stage's time summed over the tenants)."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.lower" not in spans:
        return None
    return 1e3 * sum(spans["fleet.lower"]) / n
