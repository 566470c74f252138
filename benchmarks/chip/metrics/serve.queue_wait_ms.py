"""85th percentile, in ms, of the time a request waits in the engine's queue
before admission: the program's ``queue`` intervals (``t_admit - t_submit``
of each request admitted in the window)."""

import numpy as np


def read(rec):
    q = rec.program_spans.get("queue")
    return 1e3 * float(np.percentile(q, 85)) if q else None
