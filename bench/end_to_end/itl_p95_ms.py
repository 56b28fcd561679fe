"""95th percentile of every gap between consecutive output tokens of every
request, inside the window, on the host clock."""

import numpy as np


def read(served):
    gaps = served.gaps()
    return float(np.percentile(gaps, 95)) * 1e3 if gaps.size else None
