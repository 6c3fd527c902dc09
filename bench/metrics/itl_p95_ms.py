"""Gap between output tokens, 95th percentile (ms), over every gap
between consecutive tokens of any request whose later token came out in
the window (tokens are stamped when the step that made them returns)."""

import numpy as np


def read(rec):
    v = rec.token_gaps_s()
    return float(np.percentile(v, 95)) * 1e3 if v else None
