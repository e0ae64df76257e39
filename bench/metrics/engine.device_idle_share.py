"""Share of the traced window in which no operation ran on the device,
in %.  Moves ``sim_instr_per_s``."""


def read(rec):
    t = rec["trace"]
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)
