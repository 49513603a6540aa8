"""95th percentile, over every slide of the window, of the time from
the ``observe`` call that carries the window's last stride to its census
and ``alarms()`` returned (host clock, milliseconds)."""

import numpy as np


def read(record):
    if record["driver"] != "stream" or not record["slides"]:
        return None
    return 1e3 * float(np.percentile(
        [s["seconds"] for s in record["slides"]], 95))
