"""95th percentile, over every request due in the window, of the time
from when it was due to its first token on the host."""
import math

import numpy as np


def read(run):
    t = [r.first_s - r.due_s for r in run.requests
         if not math.isnan(r.first_s)]
    return float(np.percentile(t, 95)) * 1e3 if t else None
