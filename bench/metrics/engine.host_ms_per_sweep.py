"""Host time per traced sweep: the wall time of ``Session.run`` minus the
device busy time inside it, in milliseconds.  Moves ``sim_instr_per_s``."""

from harness import trace


def read(rec):
    spans = trace.span_busy(rec["trace"].merged, rec["trace"].spans,
                            "engine.sweep")
    if not spans:
        return None
    return sum(w - b for w, b in spans) / len(spans) / 1e6
