"""95th percentile of the time from due to a successful ``try_admit``."""
import math

import numpy as np


def read(run):
    t = [r.admit_s - r.due_s for r in run.requests
         if not math.isnan(r.admit_s)]
    return float(np.percentile(t, 95)) * 1e3 if t else None
