"""Bytes the traced sweeps copied from host to device, in MB per sweep:
the ``upload_bytes`` of their ``engine.dispatch`` spans over the number
of ``session.run`` spans.  Once a session's traces are resident on the
device, a sweep copies only each call's lane parameters.  None where the
program recorded no sweep, or where its dispatches do not count their
copies while it stacked traces (``engine.stack`` spans): an engine that
copied every stack again on every dispatch.  Moves ``sim_instr_per_s``."""


def read(rec):
    spans = rec["program_spans"]
    runs = [s for s in spans if s.name == "session.run"]
    dispatches = [s for s in spans if s.name == "engine.dispatch"]
    counted = [s for s in dispatches if "upload_bytes" in s.attrs]
    stacked = any(s.name == "engine.stack" for s in spans)
    if not runs or (len(counted) < len(dispatches) and stacked):
        return None
    return sum(s.attrs["upload_bytes"] for s in counted) / len(runs) / 1e6
