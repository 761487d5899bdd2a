"""A percentile (ms) of the drain-to-drain interval over all steps of the
window, by the host's clock. One interval is one step and so is shorter than
the host clock reads well: a per-layer reading, not an end-to-end metric."""


def read(ctx, percentile):
    xs = sorted(ctx["intervals"])
    if len(xs) < 20:
        return None
    return 1e3 * xs[min(len(xs) - 1, int(percentile / 100.0 * len(xs)))]
