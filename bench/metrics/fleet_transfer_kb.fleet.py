"""Bytes across the fleet program's boundary, KB per fleet tick: the
``h2d_bytes`` of every ``fleet.dispatch`` span and the ``d2h_bytes`` of
every ``fleet.fetch`` span."""


def read(inputs):
    attrs, n = inputs.get("attrs") or {}, inputs.get("ticks")
    if not n or "fleet.dispatch" not in attrs:
        return None
    h2d = attrs["fleet.dispatch"].get("h2d_bytes", 0)
    d2h = attrs.get("fleet.fetch", {}).get("d2h_bytes", 0)
    return (h2d + d2h) / 1e3 / n
