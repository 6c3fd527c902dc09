"""Device busy time inside each `bench.decode` span, per call (ms)."""


def read(rec):
    spans = rec.spans("decode")
    busy = sum(rec.span_device_ns(s) for s in spans)
    return busy / len(spans) * 1e-6 if busy else None
