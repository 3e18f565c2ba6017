"""Latent attention's share of its HBM roofline in the decode program: the
latent cache a step must read (``lib.flops_mla.latent_cache_bytes`` of
positions 0..pos, averaged over the wave's positions) over the HBM peak,
over the device time per execution of the ops under ``latent_attention``
in ``*decode_step*`` (their share of the program, ``mla_decode_share``,
times the program's device time per execution)."""
from lib import scopes as S
from lib import trace as T


def read(R):
    if not R.trace:
        return None
    space = S.read_xspace(R.trace_dir)
    share = S.scope_share(space, "decode_step", "latent_attention") if space else None
    runs, secs = 0, 0.0
    for dev in R.trace["devices"].values():
        n, s = T.module_stats(dev, "decode_step")
        runs, secs = runs + n, secs + s
    if share is None or not runs or secs <= 0:
        return None
    scoped_s = share / 100.0 * secs / runs
    least_s = sum(R.latent_cache_bytes) / len(R.latent_cache_bytes) / R.peaks["hbm_bytes_s"]
    return 100.0 * least_s / scoped_s
