"""tpot_p90_ms: 90th percentile, over the window's requests, of (last
token stamp - first token stamp) / (tokens - 1), in milliseconds."""
from benchmarks.chip.stats import percentile


def read(run):
    per = [(t[-1] - t[0]) / (len(t) - 1) * 1e3 for w in run.waves
           for t in w.tokens.values() if len(t) > 1]
    return percentile(per, 90)
