"""Share of the traced serve window in which the device ran no op while the
host was inside a ``repro/serve/readback`` span (the per-slot token reads),
averaged over the chips."""
from lib import scopes as S


def read(R):
    if not R.trace or not R.trace["devices"]:
        return None
    spans = S.host_spans(R.trace, "repro/serve/readback")
    if not spans:
        return None
    devs = list(R.trace["devices"].values())
    return 100.0 * sum(S.idle_under_s(d, spans) for d in devs) / len(devs) / R.window_s
