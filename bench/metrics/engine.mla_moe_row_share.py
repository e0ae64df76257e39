"""Share of the traced sweeps' prepared scan rows that belong to the
absorbed-MLA decode (``net:mla``) and expert (``net:expert``) kernels, in
%: the ``rows:net:mla`` and ``rows:net:expert`` counters of the program's
``session.prepare`` spans over the prepared rows ``engine_sweep`` counted in
the same sweeps.  None where the program recorded no spans, or its
``session.prepare`` spans lack the row counters.  Moves
``sim_instr_per_s``."""

FAMILIES = ("rows:net:mla", "rows:net:expert")


def read(rec):
    spans = rec["program_spans"]
    rows = rec["counts"].get("rows")
    prepare = [s for s in spans if s.name == "session.prepare"]
    if not spans or not rows or any("rows" not in s.attrs for s in prepare):
        return None
    mine = sum(s.attrs.get(f, 0) for s in prepare for f in FAMILIES)
    return 100.0 * mine / rows
