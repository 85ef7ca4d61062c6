"""Per-tenant telemetry, constraint pass, lowering and problem build, ms
per fleet tick: the fleet runtime's ``fleet.ingest`` span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.ingest" not in spans:
        return None
    return 1e3 * sum(spans["fleet.ingest"]) / n
