"""Bytes the service moved between host and device, as a multiple of
the bytes submitted: the ``h2d_bytes`` of its ``submit`` and ``h2d``
spans and the ``d2h_bytes`` of its ``pull`` spans, over the
``payload_bytes`` of its ``submit`` spans.  A child span inherits its
parent's attributes, so each counter is summed over the spans that set
it, by name."""

#: (span name, counter) pairs of the copies.
COPIES = (("submit", "h2d_bytes"), ("h2d", "h2d_bytes"),
          ("pull", "d2h_bytes"))


def _total(spans, name: str, key: str) -> float:
    return sum(s.attrs.get(key, 0) for s in spans if s.name == name)


def read(run):
    payload = _total(run.spans, "submit", "payload_bytes")
    if payload <= 0:
        return None
    return sum(_total(run.spans, n, k) for n, k in COPIES) / payload
