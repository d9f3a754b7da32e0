"""decode_roofline: the least time the traced fused decode blocks could
take on the cell's chips (the larger of their needed bytes over peak
bandwidth and needed operations over peak compute, each one chip's
times ``chips``: weights once per step, each active sequence's live
KV), over the device time of the decode-block program's runs. It reads
the same work whatever implements attention, so weights every chip
reads again show as lost bandwidth."""

PROGRAM = "decode_steps_paged"


def read(run):
    r, w = run.reduced, run.work
    if r is None or w is None or w.decode_bytes <= 0:
        return None
    secs = r.program_s.get(PROGRAM, 0.0)
    if secs <= 0:
        return None
    n = run.cell.chips
    least = max(w.decode_bytes / (n * run.peak["hbm_bytes_per_s"]),
                w.decode_flops / (n * run.peak["bf16_flops_per_s"]))
    return 100.0 * least / secs
