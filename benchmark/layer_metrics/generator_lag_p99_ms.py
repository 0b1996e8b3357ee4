"""Load generator: 99th percentile of (actual send - due instant) over the
window's pods, on the generator's own clock. A starved generator must not be
read as a fast server."""

import numpy as np


def read(obs):
    lag = (obs.get("generator") or {}).get("lag_ms")
    if not lag:
        return None
    return float(np.percentile(np.asarray(lag, float), 99))
