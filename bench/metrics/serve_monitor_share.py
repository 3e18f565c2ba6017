"""Host time the monitor took from the served job: the sum over the window
of the program's ``repro_frame_stage_us`` stage spans, over the window."""


def read(R):
    spent_us = sum(s for s, n in R.stage_delta.values())
    if not any(n for s, n in R.stage_delta.values()):
        return None
    return 100.0 * spent_us / 1e6 / R.window_s
