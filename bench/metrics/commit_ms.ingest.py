"""Mean over writes acknowledged in the window of apply_async to the
return of wait()."""

import numpy as np


def read(run):
    if run.w_ack is None or not len(run.w_ack):
        return None
    ok = np.nan_to_num(run.w_ack, nan=np.inf) <= run.window_s
    if not ok.any():
        return None
    return 1e3 * float(np.mean(run.w_ack[ok] - run.w_submit[ok]))
