"""Share of the decode program's device time spent in latent attention: the
ops under the ``latent_attention`` name scope (scores, softmax and values
over the latent cache) in the executions of ``*decode_step*``, over those
executions' device time.  Read from the op metadata of the window's trace;
None where no op carries the scope."""
from lib import scopes as S


def read(R):
    if not R.trace:
        return None
    space = S.read_xspace(R.trace_dir)
    return S.scope_share(space, "decode_step", "latent_attention") if space else None
