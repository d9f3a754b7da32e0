"""decode_occupancy: tokens the fused decode blocks emitted over the
slots they ran (decode steps times max_batch), from the engine's
counters over the traced wave. Each request's first token comes from
prefill and is left out."""


def read(run):
    c = run.counters
    slots = c["decode_steps"] * run.cell.sizes["max_batch"]
    if slots <= 0:
        return None
    return 100.0 * (c["new_tokens"] - c["requests"]) / slots
