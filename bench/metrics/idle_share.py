"""idle_share: percent of the traced window in which no operation ran on
the device, averaged over the chips used (layer: device)."""


def read(ctx):
    red = ctx.reduced()
    if red.window_ns <= 0 or red.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
