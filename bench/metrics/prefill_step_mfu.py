"""Whole prefill against the chip's peak (%): the least time of the
needed work by the configuration's cost model (for a dense decoder: real
prompt tokens through every layer, causal attention, one row of logits)
over the device time inside the `bench.prefill` spans."""

from bench import flops


def read(rec):
    spans = rec.spans("prefill")
    dev = sum(rec.span_device_ns(s) for s in spans) * 1e-9
    if not dev:
        return None
    need = sum(flops.least_time(*rec.cost.prefill_cost(rec.model, int(s[3]["tokens"])), rec.peak)
               for s in spans)
    return 100.0 * need / dev
