"""95th percentile over requests of (last token - first token) /
(tokens - 1), for every request that got two tokens or more."""
import numpy as np


def read(run):
    t = [(r.last_s - r.first_s) / (r.tokens - 1) for r in run.requests
         if r.tokens >= 2]
    return float(np.percentile(t, 95)) * 1e3 if t else None
