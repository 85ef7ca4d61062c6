"""Per-tenant telemetry and constraint pass, ms per fleet tick: the fleet
runtime's ``fleet.telemetry`` and ``fleet.constraints`` spans (each the
stage's time summed over the tenants)."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.constraints" not in spans:
        return None
    return 1e3 * (sum(spans.get("fleet.telemetry", ()))
                  + sum(spans["fleet.constraints"])) / n
