"""Shared benchmark utilities — now a thin shim over the process-default
:class:`repro.api.Session`.

The two-level sweep cache this module used to own (module-global ``_BUILT``
/ ``_PREPARED`` dicts) lives in the Session now: trace preparation is keyed
by (name, params, fold, max_events, fold warm-up — a function of the static
L1 geometry only), and compiled executables live in XLA's jit cache, one
entry per (shape bucket, L1 geometry) signature.  Suites that still sweep
through this module share the default Session's caches; new code should
construct a :class:`repro.api.Sweep` and call ``Session.run`` directly.
"""

from __future__ import annotations

import warnings

from repro import api
from repro.core import simulator

# The refine budget lives on the Session now: tune it via
# api.default_session().refine_max_rows (or a Session of your own).


def built(name):
    """Build (and cache) a paper-size benchmark trace."""
    return api.default_session().built(name)


def prepared_for(name, fold=True, max_events=None,
                 machine=simulator.DEFAULT_MACHINE) -> simulator.PreparedTrace:
    """Prepared (expanded + folded) trace per benchmark, session-cached.

    ``max_events`` truncation is deprecated here: declare the budget on a
    :class:`repro.api.Sweep` (``Sweep(max_events=...)``) instead.
    """
    if max_events is not None:
        warnings.warn(
            "prepared_for(max_events=...) is deprecated; pass max_events to "
            "a repro.api.Sweep (or Session.prepared) instead",
            DeprecationWarning, stacklevel=2)
    return api.default_session().prepared(name, fold=fold,
                                          max_events=max_events,
                                          machine=machine)


def sweep_grid(names, sweep, fold=True, max_events=None, refine=True,
               machine=simulator.DEFAULT_MACHINE):
    """One sweep call for a whole suite: P programs x C configs — and, when
    ``machine`` is a :class:`simulator.MachineSweep`, x M machine points in
    the same dispatch.  Delegates to ``Session.grid`` on the process-default
    session (which owns the caches and the fold/refine policy)."""
    return api.default_session().grid(names, sweep, machine=machine,
                                      fold=fold, max_events=max_events,
                                      refine=refine)


def emit(rows: list[dict], header: list[str]) -> None:
    print(",".join(header))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in header))
