"""idle_host_share: percent of the traced window in which the device was
idle while the scheduler's worker did host work of its own: admitting,
assembling a step's batch, dispatching it, selecting tokens from its
logits (layer: scheduler, ``PagedBatcher``)."""
from bench import idle

SPANS = r"^sched\.(admit|assemble|dispatch|select)$"


def read(ctx):
    return idle.share(ctx, SPANS)
