"""Time to first token, 90th percentile (ms), over every request due in
the window, counted from the time it was due.  A request with no first
token when the window closes counts at its age then; one that failed
counts as the window's whole length."""

import numpy as np


def read(rec):
    v = rec.ttft_s()
    return float(np.percentile(v, 90)) * 1e3 if v else None
