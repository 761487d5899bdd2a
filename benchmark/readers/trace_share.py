"""A share (%) read from the reduced trace: `field` is a share already
(idle_share) or seconds to be put over the traced window (`over_window`)."""


def read(ctx, field, over_window=False):
    red = ctx["trace"]
    if red is None:
        return None
    v = red[field]
    return 100.0 * (v / red["window_s"] if over_window else v)
