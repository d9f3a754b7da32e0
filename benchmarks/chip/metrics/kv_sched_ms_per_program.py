"""kv_sched_ms_per_program: milliseconds the KV manager and the
scheduler held the host, per program run, in the traced slices: the
seconds in which any ``kv.*`` or ``sched.*`` span ran (profiler host
plane, ``host_spans.py``; nested spans count once), over the runs of the
prefill and decode programs there (device trace)."""
from benchmarks.chip import host_spans


def read(run):
    h, n = host_spans.of(run), host_spans.program_runs(run)
    if h is None or n <= 0:
        return None
    return 1e3 * h.layer_union_s / n
