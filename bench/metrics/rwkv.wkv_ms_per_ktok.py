"""rwkv.wkv_ms_per_ktok: device time of the WKV recurrence (the ops of
the ``rwkv.wkv`` scope inside ``jit_prefill``) per thousand prompt
tokens, over the window's admissions whose prefill the trace holds
whole (``bench/flops_rwkv6.py`` ``wkv_prefills``)."""

from bench import flops_rwkv6


def read(ctx):
    got = flops_rwkv6.wkv_prefills(ctx)
    if not got:
        return None
    tokens = sum(n for n, _ in got)
    return sum(s for _, s in got) * 1e3 / (tokens / 1000.0)
