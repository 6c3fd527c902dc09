"""Wait for admission, 90th percentile (ms), over every request due in
the window: from the time it was due to the start of its first prefill,
as the client's submit stamp (drive clock) plus the engine's own
`t_admit - t_submit` (its clock).  One not admitted when the window
closes counts at its age then.  The twin of `queue_wait_p90_ms`, which
reads the benchmark's wrapper; nothing where requests carry no
`t_admit`."""

import math

import numpy as np


def read(rec):
    due = rec.due_in_window()
    if not due or not all(hasattr(tr.req, "t_admit") for tr in due):
        return None
    v = []
    for tr in due:
        req = tr.req
        if req.t_admit is None or math.isnan(tr.submit):
            v.append(rec.hi - tr.due)
        else:
            v.append(tr.submit - tr.due + req.t_admit - req.t_submit)
    return float(np.percentile(v, 90)) * 1e3
