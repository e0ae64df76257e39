"""Bytes a decode step must move (every weight matrix and the whole
key/value cache it attends over, closed form from the configuration's
sizes) over the device time of a step, as a share of the chip's HBM
bandwidth, in %.  Moves ``decode_tokens_per_s``."""

import pathlib

from harness import costs, spec

_step = spec.load_module(pathlib.Path(__file__).with_name(
    "serve.decode_step_ms.py"))


def read(rec):
    s = _step.step_busy_s(rec)
    if not s:
        return None
    c = rec["counts"]
    need = costs.decode_step_bytes(rec["config"], c["slots"], c["max_len"])
    return 100.0 * need / s / rec["peaks"]["hbm_bytes_per_s"]
