"""spec_accept_share: percent of drafted tokens the verifier accepted over
the traced part of the window.  Defined only here, to show that a metric
is added as a file and an entry."""


def read(ctx):
    proposed = ctx.counters.get("spec_proposed", 0.0)
    if proposed <= 0:
        return None
    return 100.0 * ctx.counters["spec_accepted"] / proposed
