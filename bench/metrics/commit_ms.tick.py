"""Hysteresis switch and emissions accounting, ms per eager tick: the
runtime's ``switch`` and ``account`` spans."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "account" not in spans:
        return None
    return 1e3 * (sum(spans.get("switch", ())) + sum(spans["account"])) / n
