"""Host time per decode step spent dispatching the next step and its argmax:
the summed ``repro/serve/dispatch`` host spans of the traced window over its
``serve/decode_step`` spans (both the program's own, on the trace's clock)."""
from lib import scopes as S


def read(R):
    return S.per_step_ms(R.trace, "repro/serve/dispatch") if R.trace else None
