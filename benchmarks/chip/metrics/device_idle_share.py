"""Device: 1 - (union of op intervals) / traced window, averaged over the
chips."""


def read(view):
    tr = view.trace
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
