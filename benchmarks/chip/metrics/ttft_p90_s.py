"""ttft_p90_s: 90th percentile, over every request of the window, of its
first token's host stamp less its wave's issue time."""
from benchmarks.chip.stats import percentile


def read(run):
    return percentile([w.tokens[r][0] - w.t_issue for w in run.waves
                       for r in range(len(w.prompts)) if w.tokens.get(r)], 90)
