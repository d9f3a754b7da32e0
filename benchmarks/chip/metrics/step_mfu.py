"""step_mfu: model operations the traced slices' prefill chunks and
decode steps needed (the family's counts), over the slices' wall time,
as a share of the cell's chips' bf16 peak (one chip's times ``chips``).
It bounds any one program's gain."""


def read(run):
    r, w = run.reduced, run.work
    if r is None or w is None or r.window_s <= 0 or r.busy_s <= 0:
        return None
    flops = w.prefill_flops + w.decode_flops
    if flops <= 0:
        return None
    peak = run.cell.chips * run.peak["bf16_flops_per_s"]
    return 100.0 * flops / r.window_s / peak
