"""Share of the traced window in which no operation runs on the chip (%)."""


def read(rec):
    return 100.0 * (1.0 - rec.device_busy_fraction())
