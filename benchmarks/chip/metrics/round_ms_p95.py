"""95th percentile of the whole-round wall time over the window's rounds."""

import numpy as np


def read(ctx):
    return float(np.percentile(np.asarray(ctx.rounds_s) * 1e3, 95))
