"""The whole decode step's share of the bf16 peak: model FLOPs of every
decode step in the window (``lib.flops.decode_step_flops`` at its position)
over the summed time between output tokens, over the peak.  It bounds
``decode_hbm_roofline``, which goes silent where the decode program
changes its name."""


def read(R):
    if not R.gaps_ms.size:
        return None
    steps_per_wave = len(R.decode_step_flops) - 1  # a wave's first step starts no gap
    flops = R.waves_in_window * sum(R.decode_step_flops[1:])
    assert R.gaps_ms.size == R.waves_in_window * steps_per_wave
    return 100.0 * flops / (R.gaps_ms.sum() / 1e3) / R.peaks["bf16_flops"]
