"""1 minus the union of the device's op intervals over the traced window, in
percent, the mean over the chips."""


def read(ctx):
    window = ctx.window_s()
    if not ctx.devices or not window:
        return None
    return 100.0 * (1.0 - ctx.busy_s() / window)
