"""Device idle share of the traced window, in %: 1 - the union of the
device's op intervals over the window, averaged over the cell's chips."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share()
