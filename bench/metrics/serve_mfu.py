"""Model FLOPs of the serve window (``lib.flops.wave_flops`` per wave, from
the configuration's shapes) over window seconds over the bf16 peak."""


def read(R):
    flops = R.waves_in_window * R.wave_flops["total"]
    return 100.0 * flops / R.window_s / R.peaks["bf16_flops"]
