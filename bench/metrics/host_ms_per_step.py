"""Host time per engine step (ms): the part of each `bench.step` span in
which the chip is not busy, averaged over the steps."""


def read(rec):
    return rec.host_ms_per_step()
