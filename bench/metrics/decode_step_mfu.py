"""Whole decode step against the chip's peak (%): the least time the
step's needed work takes (the larger of its flops over peak flops and
its bytes over peak bandwidth, by the configuration's cost model: for a
dense decoder the weights read once plus the live KV of the active
requests, not the gathered capacity) over the device time inside the
`bench.decode` spans."""

from bench import flops


def read(rec):
    spans = rec.spans("decode")
    dev = sum(rec.span_device_ns(s) for s in spans) * 1e-9
    if not dev:
        return None
    need = sum(flops.least_time(*rec.cost.decode_cost(rec.model, int(s[3]["active"]),
                                                      int(s[3]["ctx"])), rec.peak)
               for s in spans)
    return 100.0 * need / dev
