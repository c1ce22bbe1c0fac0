"""95th percentile (nearest rank) of due-to-bind time over every task that
fell due in the window; one still pending at the close counts at its age
then. Only open-loop traffic has due times."""

import math


def read(run):
    lat = sorted(run.latencies or [])
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1] * 1e3
