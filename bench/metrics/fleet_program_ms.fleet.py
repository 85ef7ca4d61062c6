"""The fleet program's calls, ms per fleet tick: every ``fleet.dispatch``
(arguments to the chips, enqueue), ``fleet.wait`` (``block_until_ready``)
and ``fleet.fetch`` (outputs to the host) span."""


def read(inputs):
    spans, n = inputs.get("spans") or {}, inputs.get("ticks")
    if not n or "fleet.dispatch" not in spans:
        return None
    return 1e3 * sum(sum(spans.get(k, ())) for k in (
        "fleet.dispatch", "fleet.wait", "fleet.fetch")) / n
