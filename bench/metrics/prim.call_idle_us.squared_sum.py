"""prim.call_idle_us.squared_sum: median time the device idles in one
``squared_sum`` call, from the call's start to the next call's, less
the device time of its own programs; free of the profiler's skew
between the host's and the device's timelines
(``bench/program_spans.py`` ``call_idle``)."""

from bench import program_spans as P


def read(ctx):
    return P.median_call_idle_us(ctx, "squared_sum")
