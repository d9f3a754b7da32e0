"""host_ms_per_program: milliseconds the engine loop spent on the host
outside its programs, per program run, in the traced slices: the seconds
of its ``engine.*`` phases other than ``*.run`` (profiler host plane,
``host_spans.py``), over the runs of the prefill and decode programs
there (device trace)."""
from benchmarks.chip import host_spans


def read(run):
    h, n = host_spans.of(run), host_spans.program_runs(run)
    if h is None or n <= 0:
        return None
    return 1e3 * h.host_s / n
