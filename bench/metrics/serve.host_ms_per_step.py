"""Host time per traced ``ServeEngine.step``: its wall time minus the
device busy time inside it, in milliseconds.  Moves ``tpot_p95_ms``."""

from harness import trace


def read(rec):
    spans = trace.span_busy(rec["trace"].merged, rec["trace"].spans,
                            "serve.step")
    if not spans:
        return None
    return sum(w - b for w, b in spans) / len(spans) / 1e6
