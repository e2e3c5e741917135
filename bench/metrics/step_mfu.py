"""step_mfu: model FLOPs of the useful tokens computed in the traced part of
the window, over its length times the chip's peak (layer: device step,
``paged_step``).

Useful tokens are the prompt tokens computed (not those reused from the
prefix cache) and the output tokens; their FLOPs include attention over
the live context and, for each output token, the output head.  Prompt and
decode tokens are counted from the inputs of each mixed or decode step;
a verify step counts each live row's pending token and, spread over the
verify rows' contexts, the drafts accepted (``spec_accepted``).  Padding
rows and tokens and rejected drafts do not count.  A token's FLOPs are its
family's count (``token_flops``, ``head_flops``): active weights only.
"""
import numpy as np


def read(ctx):
    if not ctx.steps or ctx.window_s <= 0:
        return None
    m, fam = ctx.dims, ctx.family
    total, verify_ctx = 0.0, []
    for kind, live, pos, last in ctx.steps:
        for i in range(len(live)):
            if live[i] == 0:
                continue
            if kind == "verify":
                verify_ctx.append(pos[i, 0] + 1)
                total += float(fam.token_flops(m, pos[i, 0] + 1))
            else:
                total += float(np.sum(fam.token_flops(
                    m, pos[i, :int(last[i]) + 1] + 1)))
    accepted = ctx.counters.get("spec_accepted", 0.0)
    if accepted and verify_ctx:
        total += accepted * float(fam.token_flops(m, np.mean(verify_ctx)))
    outputs = ctx.counters.get("batched_rows", 0.0) + accepted
    total += outputs * fam.head_flops(m)
    return 100.0 * total / (ctx.window_s * ctx.peak["bf16_flops_per_s"])
