"""The benchmark's yardstick: cell lookup by name, spans, the reduction
from profiler trace to metrics, the peak table, closed-form costs, the
plain references and the comparisons that decide ``correct``.

Nothing here is imported by the program under test; the drivers under
``bench/drivers`` call into the program through its public entry points.
"""
