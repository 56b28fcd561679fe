"""90th percentile of time to first token over every request due in the
window: from its due time to its first token on the host. One that failed, or
is still waiting when the window closes, counts at what it waited by then."""

import numpy as np


def read(served):
    return float(np.percentile(served.ttfts(), 90))
