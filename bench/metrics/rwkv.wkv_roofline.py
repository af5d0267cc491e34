"""rwkv.wkv_roofline: the WKV recurrence's share of its roofline in
the admission prefills the trace holds whole (``bench/flops_rwkv6.py``
``wkv_prefills``).  Its least time is the larger of the FLOPs it needs
over the bf16 peak and the bytes it must move over the HBM bandwidth
(r, k, v, w in and y out in f32 per token and layer, the state once
per sequence: ``Finch.wkv_flops``, ``Finch.wkv_bytes``), summed over
those prompts, over the device time of its ops."""

from bench import flops_rwkv6


def read(ctx):
    got = flops_rwkv6.wkv_prefills(ctx)
    if not got:
        return None
    fin, p = ctx["record"].dec, ctx["peaks"]
    ideal = max(sum(fin.wkv_bytes(n) for n, _ in got)
                / p["hbm_bytes_per_s"],
                sum(fin.wkv_flops(n) for n, _ in got) / p["bf16_flops"])
    return 100.0 * ideal / sum(s for _, s in got)
