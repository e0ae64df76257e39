"""Device busy time inside the traced ``ServeEngine.step`` calls per
step, in milliseconds.  Moves ``decode_tokens_per_s``."""

from harness import trace


def step_busy_s(rec):
    spans = trace.span_busy(rec["trace"].merged, rec["trace"].spans,
                            "serve.step")
    if not spans:
        return None
    return sum(b for _, b in spans) / len(spans) / 1e9


def read(rec):
    s = step_busy_s(rec)
    return None if not s else s * 1e3
