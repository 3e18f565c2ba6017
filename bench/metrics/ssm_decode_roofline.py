"""The state-space mixer's share of its HBM roofline in the decode program:
the state a step must read and write (``lib.flops_hybrid.ssm_state_step_bytes``:
every Mamba-2 layer's float32 SSM state and conv tail, read and written)
over the HBM peak, over the device time per execution of the ops under
``ssm_state`` in ``*decode_step*`` (their share of the program times the
program's device time per execution).  None where
``drivers/serve_hybrid.py`` counts no state or no op carries the scope."""
from lib import scopes as S
from lib import trace as T


def read(R):
    state_bytes = getattr(R, "ssm_state_step_bytes", None)
    if not R.trace or not state_bytes:
        return None
    space = S.read_xspace(R.trace_dir)
    share = S.scope_share(space, "decode_step", "ssm_state") if space else None
    runs, secs = 0, 0.0
    for dev in R.trace["devices"].values():
        n, s = T.module_stats(dev, "decode_step")
        runs, secs = runs + n, secs + s
    if share is None or not runs or secs <= 0:
        return None
    scoped_s = share / 100.0 * secs / runs
    return 100.0 * state_bytes / R.peaks["hbm_bytes_s"] / scoped_s
