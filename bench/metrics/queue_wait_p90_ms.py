"""Engine queue wait, 90th percentile (ms): from the time a request due in
the window was due to the start of its prefill (stamped on the host in
every run); one not prefilled when the window closes counts at its age
then."""

import numpy as np


def read(rec):
    v = [(tr.prefill_at if tr.prefill_at is not None else rec.hi) - tr.due for tr in rec.due_in_window()]
    return float(np.percentile(v, 90)) * 1e3 if v else None
