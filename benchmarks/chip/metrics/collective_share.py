"""collective_share: device seconds of the exchanges between chips
(all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
by HLO op kind) in the traced slices, averaged over the chips, over the
device's busy seconds there (device trace). Nothing to read where no
collective ran, as on one chip."""


def read(run):
    r = run.reduced
    if r is None or r.busy_s <= 0 or r.collective_s <= 0:
        return None
    return 100.0 * r.collective_s / r.busy_s
