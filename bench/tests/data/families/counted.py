"""A family defined only under a test directory: the dense family's code,
found through the same lookup, with a count of each call to it."""
import collections

from bench import spec

_BASE = spec.load_family("dense")
calls = collections.Counter()


def _counted(name):
    fn = getattr(_BASE, name)

    def call(*a, **kw):
        calls[name] += 1
        return fn(*a, **kw)
    return call


dims = _counted("dims")
program_config = _counted("program_config")
make = _counted("make")
logits = _counted("logits")
token_flops = _counted("token_flops")
head_flops = _counted("head_flops")
attention_work = _counted("attention_work")
