"""host_ms_per_step: the scheduler worker's host milliseconds per jitted
step (``step_host_ns / step_calls``, the batcher's counters) over the
traced part of the window: admission, batch assembly, dispatch and token
selection, the time the synchronous loop makes the device wait for
(layer: scheduler, ``PagedBatcher``)."""


def read(ctx):
    calls = ctx.counters.get("step_calls", 0.0)
    if calls <= 0:
        return None
    return ctx.counters["step_host_ns"] / calls / 1e6
