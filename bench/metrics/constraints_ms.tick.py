"""Telemetry ingest and constraint pass, ms per eager tick: the runtime's
``telemetry.ingest`` and ``constraints`` spans."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "constraints" not in spans:
        return None
    return 1e3 * (sum(spans.get("telemetry.ingest", ()))
                  + sum(spans["constraints"])) / n
