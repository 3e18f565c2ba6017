"""Share of the traced serve window in which no operation ran on the device:
1 - (union of device op intervals) / window, averaged over the chips."""
from lib import trace as T


def read(R):
    if not R.trace or not R.trace["devices"]:
        return None
    return 100.0 * (1.0 - T.mean_busy_s(R.trace) / R.window_s)
