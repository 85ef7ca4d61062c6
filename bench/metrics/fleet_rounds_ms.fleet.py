"""The price rounds, ms per fleet tick: every ``fleet.round`` span (price
fold and stacking, the program calls, the load and price update)."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.round" not in spans:
        return None
    return 1e3 * sum(spans["fleet.round"]) / n
