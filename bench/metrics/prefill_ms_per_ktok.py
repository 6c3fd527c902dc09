"""Device busy time inside `bench.prefill` spans per 1000 real prompt
tokens (ms/ktok)."""


def read(rec):
    spans = rec.spans("prefill")
    tokens = sum(int(s[3]["tokens"]) for s in spans)
    busy = sum(rec.span_device_ns(s) for s in spans)
    return busy * 1e-6 / (tokens / 1000) if busy else None
