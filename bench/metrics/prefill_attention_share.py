"""Share of the prefill program's device time spent in attention: the ops
under the ``attention`` name scope (projections, latent expansion and the
causal attention itself) in the executions of ``*prefill*``, over those
executions' device time.  Read from the op metadata of the window's
trace; None where no op carries the scope."""
from lib import scopes as S


def read(R):
    if not R.trace:
        return None
    space = S.read_xspace(R.trace_dir)
    return S.scope_share(space, "prefill", "attention") if space else None
