"""Share of the decode program's device time spent casting the layer
weights to the compute dtype: the ops under the program's ``cast_params``
name scope in the executions of ``*decode_step*``, over those executions'
device time.  Read from the op metadata of the window's profiler trace."""
import os

from lib import scopes as S

TRACES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".traces")


def read(R):
    if not R.trace:
        return None
    space = S.read_xspace(os.path.join(TRACES, R.cell["name"]))  # as bench/drivers/serve.py writes it
    return S.scope_share(space, "decode_step", "cast_params") if space else None
