"""Share of the lane-rows the planner issued that carry a trace row
rather than bucket padding, in %: sum of the prepared traces' rows over
sum of bucket x programs per dispatch, for the traced sweeps.  An exact
count.  Moves ``sim_instr_per_s``."""


def read(rec):
    c = rec["counts"]
    if not c["padded_rows"]:
        return None
    return 100.0 * c["rows"] / c["padded_rows"]
