"""How late the load generator submitted each chunk of the window: 99th
percentile of submit time - due time, on the harness's clock. A knee set
by the generator shows here, not as a slow server."""

import numpy as np


def read(view):
    lag = view.records["summary"]["gen_lag_s"]
    return 1e3 * float(np.percentile(lag, 99)) if len(lag) else None
