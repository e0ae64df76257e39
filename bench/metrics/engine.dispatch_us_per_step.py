"""Wall of the traced sweeps' ``engine.dispatch`` spans that compiled
nothing, over the bucket rows they scanned, in microseconds per scan
step (``harness.program_spans``).  Moves ``sim_instr_per_s``."""

from harness import program_spans


def read(rec):
    return program_spans.dispatch_us_per_step(rec["program_spans"])
