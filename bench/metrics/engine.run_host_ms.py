"""Host self-time of a traced sweep: the ``session.run`` span's wall minus
the ``engine.dispatch`` walls under it, mean over sweeps, in milliseconds
(``harness.program_spans``).  Moves ``sim_instr_per_s``."""

from harness import program_spans


def read(rec):
    return program_spans.run_host_ms(rec["program_spans"])
