"""host_syncs_per_tok: device-to-host round trips the engine took per
output token, from its counters over the traced wave."""


def read(run):
    c = run.counters
    return c["host_syncs"] / c["new_tokens"] if c["new_tokens"] else None
