"""idle_host_share: share of the traced slices' device idle time (no
operation on the first chip) that lies under an ``engine.*`` phase other
than ``*.run``: the idle time the engine loop's own host work leaves
(profiler trace, ``host_spans.py``)."""
from benchmarks.chip import host_spans


def read(run):
    h = host_spans.of(run)
    if h is None or h.idle_s <= 0:
        return None
    return 100.0 * h.idle_host_s / h.idle_s
