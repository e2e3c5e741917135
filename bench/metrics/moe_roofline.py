"""moe_roofline: percent of the expert layer's device time that the chip's
roofline needs for the held experts' work of the steps traced (layer:
kernels, ``models/moe.py`` ``moe_ffn_dropless``).

Work: for each step, the valid tokens of its live rows, counted from the
step's inputs at the jitted-step boundary by the cell's family
(``expert_work``: the layers, and per layer the FLOPs of the (token, held
expert) pairs and the bytes of every held expert's weights); padding rows
and tokens do not count.  Time: every device op the trace names as the
grouped expert matmul (``jax.lax.ragged_dot``: its metadata and its
matmuls).  A family without ``expert_work``, or a trace without the op,
reads nothing.
"""
from bench import flops

KERNELS = r"^ragged-dot"


def read(ctx):
    work = getattr(ctx.family, "expert_work", None)
    if work is None or not ctx.steps:
        return None
    ns = ctx.reduced({"experts": KERNELS}).kernel_ns["experts"]
    if ns <= 0:
        return None
    need = 0.0
    for _, live, pos, last in ctx.steps:
        rows = [pos[i, :int(last[i]) + 1] for i in range(len(live))
                if live[i] != 0]
        count, f, b = work(ctx.dims, rows)
        need += count * flops.roofline_seconds(f, b, ctx.peak)
    return 100.0 * need / (ns / 1e9)
