"""prefill_mfu: operations the traced prefill chunks needed (matmuls and
causal attention at each token's position), over the device time of the
prefill-chunk program's runs, as a share of the cell's chips' bf16 peak
(one chip's times ``chips``: matmuls every chip repeats count once)."""

PROGRAM = "prefill_paged_chunk"


def read(run):
    r, w = run.reduced, run.work
    if r is None or w is None or w.prefill_flops <= 0:
        return None
    secs = r.program_s.get(PROGRAM, 0.0)
    if secs <= 0:
        return None
    peak = run.cell.chips * run.peak["bf16_flops_per_s"]
    return 100.0 * w.prefill_flops / secs / peak
