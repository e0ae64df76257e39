"""Device busy time inside the traced sweeps over the scan steps their
recorded plans issued, in microseconds per step.  The scan of a fused
dispatch advances all its lanes together, so a step is one bucket row.
Refinement re-simulations, if any, run inside the sweep and count in the
numerator but not in the plan's steps.  Moves ``sim_instr_per_s``."""

from harness import trace


def read(rec):
    steps = rec["counts"]["scan_steps"]
    busy = sum(b for _, b in trace.span_busy(rec["trace"].merged,
                                             rec["trace"].spans,
                                             "engine.sweep"))
    if not steps or not busy:
        return None
    return busy / steps / 1e3
