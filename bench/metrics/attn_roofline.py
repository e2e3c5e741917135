"""attn_roofline: percent of the paged-attention kernels' device time that
the chip's roofline needs for the attention work of the steps traced
(layer: kernels, ``kernels/paged_attention.py``).

Work: for each step, each live row's valid query tokens against their live
context (a token at position p reads p + 1 keys), counted from the step's
inputs at the jitted-step boundary by the cell's family
(``attention_work``: each kind of attention layer's count, FLOPs and
bytes); padding rows, padding tokens and block over-reads do not count.
Time: every device op the trace names as one of the paged-attention
kernels, decode and prefill alike, so the same work is read if one ragged
kernel replaces both.
"""
from bench import flops

# the decode and the prefill/verify kernels, as the trace names them
KERNELS = r"^paged_(prefill_)?attention$"


def read(ctx):
    ns = ctx.reduced({"attn": KERNELS}).kernel_ns["attn"]
    if ns <= 0 or not ctx.steps:
        return None
    need = 0.0
    for _, live, pos, last in ctx.steps:
        rows = [pos[i, :int(last[i]) + 1] for i in range(len(live))
                if live[i] != 0]
        for count, f, b in ctx.family.attention_work(ctx.dims, rows):
            need += count * flops.roofline_seconds(f, b, ctx.peak)
    return 100.0 * need / (ns / 1e9)
