"""prefill_mfu: operations the traced prefill chunks needed (matmuls and
causal attention at each token's position), over the device time of the
prefill-chunk program's runs, as a share of the chip's bf16 peak."""

PROGRAM = "prefill_paged_chunk"


def read(run):
    r, w = run.reduced, run.work
    if r is None or w is None or w.prefill_flops <= 0:
        return None
    secs = r.program_s.get(PROGRAM, 0.0)
    if secs <= 0:
        return None
    return 100.0 * w.prefill_flops / secs / run.peak["bf16_flops_per_s"]
