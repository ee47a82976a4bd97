"""Bytes the service moved from one chip to another, as a multiple of
the bytes submitted: the ``d2d_bytes`` of its ``stack`` and ``h2d`` spans
over the ``payload_bytes`` of its ``submit`` spans.  0 when every payload
was put on the chip of the worker that ran it; None where the service
counts no such bytes."""

#: Spans whose ``d2d_bytes`` count a chip-to-chip copy.
MOVES = ("stack", "h2d")


def read(run):
    moved = [s.attrs["d2d_bytes"] for s in run.spans
             if s.name in MOVES and "d2d_bytes" in s.attrs]
    payload = sum(s.attrs.get("payload_bytes", 0) for s in run.spans
                  if s.name == "submit")
    if not moved or payload <= 0:
        return None
    return sum(moved) / payload
