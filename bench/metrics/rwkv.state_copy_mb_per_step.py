"""rwkv.state_copy_mb_per_step: megabytes the paged store's eager
copies of dense per-slot state (``write_slot`` at admission,
``write_token`` after each decode step; counter ``store.state_bytes``,
the whole leaf per copy) moved while the window was open, per batched
decode step of the window."""


def read(ctx):
    work = ctx["work"]
    if not ctx.get("trace") or not work.get("decode_steps") \
            or "state_bytes" not in work:
        return None
    return work["state_bytes"] / work["decode_steps"] / 1e6
