"""The forward flash-attention Pallas kernel's share of its roofline: the
least time its calls could take (each call's FLOPs over the bf16 peak or
its bytes over HBM bandwidth, whichever is larger; ``flops.py``) over
the device time of its events in the trace. Its backward pass runs the
reference attention and is not counted."""

from chipbench import flops, tracing


def read(view):
    evs = tracing.ops_named(view.trace, "flash_attention")
    if not evs:
        return None
    f = view.facts
    fl, nb = flops.flash_forward_cost(f["model"], f["batch_per_worker"], f["seq"])
    t_min, _ = flops.roofline_seconds(fl, nb, view.peak)
    spent = sum(e - s for _, s, e in evs) / 1e9
    return 100.0 * len(evs) * t_min / spent
