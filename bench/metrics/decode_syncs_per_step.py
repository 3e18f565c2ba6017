"""Device-to-host reads the serve loop issues per decode step: the program's
``repro_serve_host_syncs_total`` over ``repro_serve_decode_steps_total``.
Both count from the start of the process (the warm-up call and the
measured one); a ratio of counts made alike in every step is the window's."""

SYNCS, STEPS = "repro_serve_host_syncs_total", "repro_serve_decode_steps_total"


def per_step(snapshot: dict):
    """The ratio in a registry snapshot; None where either counter is absent
    or no step was counted."""
    syncs = snapshot.get(SYNCS, {}).get("series", {}).get("[]")
    steps = snapshot.get(STEPS, {}).get("series", {}).get("[]")
    return syncs / steps if syncs is not None and steps else None


def read(R):
    from repro.telemetry.registry import get_registry

    return per_step(get_registry().snapshot())
