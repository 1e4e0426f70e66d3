"""95th percentile of every call in the window, from issue to
``block_until_ready``, on the host clock."""

import numpy as np


def read(run):
    lat = [c.done - c.issued for c in run.ok_calls]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
