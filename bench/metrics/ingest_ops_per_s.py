"""Edge ops of writes acknowledged (fsynced) inside the window, per
second of the window."""

import numpy as np


def read(run):
    if run.w_ack is None or not len(run.w_ack):
        return None
    acked = np.nan_to_num(run.w_ack, nan=np.inf) <= run.window_s
    return float(acked.sum()) * run.ops_per_write / run.window_s
