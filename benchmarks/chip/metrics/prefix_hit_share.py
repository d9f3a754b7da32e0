"""prefix_hit_share: prompt tokens served from the KV manager's prefix
cache over prompt tokens served (cached plus computed), from the
engine's counters over the traced wave."""


def read(run):
    c = run.counters
    total = c["cached_prefix_tokens"] + c["prefill_tokens_computed"]
    return 100.0 * c["cached_prefix_tokens"] / total if total else None
