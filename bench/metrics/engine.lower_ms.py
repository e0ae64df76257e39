"""Mean wall of a traced ``bridge.lower`` span (one model lowered into
tile programs), in milliseconds (``harness.program_spans``).  Moves
``sim_instr_per_s``."""

from harness import program_spans


def read(rec):
    return program_spans.lower_ms(rec["program_spans"])
