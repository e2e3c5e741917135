"""rows_per_step: decoding rows per step that decodes, from the batcher's
counters (``batched_rows / decode_steps``) over the traced part of the
window (layer: scheduler, ``PagedBatcher``)."""


def read(ctx):
    steps = ctx.counters.get("decode_steps", 0.0)
    if steps <= 0:
        return None
    return ctx.counters["batched_rows"] / steps
