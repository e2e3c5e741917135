"""idle_fetch_share: percent of the traced window in which the device was
idle while the scheduler's worker was in ``sched.fetch``, waiting for a
step's logits: the step's end, the logits' transfer to the host and
their layout change there (layer: device step,
``PagedBatcher._call_step``)."""
from bench import idle

SPANS = r"^sched\.fetch$"


def read(ctx):
    return idle.share(ctx, SPANS)
