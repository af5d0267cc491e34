"""prim.dispatch_us: median host time of one call into the op registry,
from the call until it returns a (not yet ready) result; the
benchmark's own span, before the sync."""

from bench import stats


def read(ctx):
    spans = ctx["work"].get("dispatch_s")
    return stats.median(spans) * 1e6 if spans else None
