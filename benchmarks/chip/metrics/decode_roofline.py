"""decode_roofline: the least time the traced fused decode blocks could
take on the chip (the larger of their needed bytes over peak bandwidth
and needed operations over peak compute: weights once per step, each
active sequence's live KV), over the device time of the decode-block
program's runs. It reads the same work whatever implements attention."""

PROGRAM = "decode_steps_paged"


def read(run):
    r, w = run.reduced, run.work
    if r is None or w is None or w.decode_bytes <= 0:
        return None
    secs = r.program_s.get(PROGRAM, 0.0)
    if secs <= 0:
        return None
    least = max(w.decode_bytes / run.peak["hbm_bytes_per_s"],
                w.decode_flops / run.peak["bf16_flops_per_s"])
    return 100.0 * least / secs
