"""Plan objects, per-tenant emissions and the capacity report after the
last round, ms per fleet tick: the ``fleet.finalize`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.finalize" not in spans:
        return None
    return 1e3 * sum(spans["fleet.finalize"]) / n
