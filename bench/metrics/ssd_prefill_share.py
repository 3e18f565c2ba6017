"""Share of the prefill program's device time spent in the SSD: the ops
under the ``ssm_scan`` name scope (the intra-chunk form, the chunk states
and the state passing of every Mamba-2 layer) in the executions of
``*prefill*``, over those executions' device time.  Read from the op
metadata of the window's trace; None where no op carries the scope."""
from lib import scopes as S


def read(R):
    if not R.trace:
        return None
    space = S.read_xspace(R.trace_dir)
    return S.scope_share(space, "prefill", "ssm_scan") if space else None
