"""The whole pool-wide decode program's share of its roofline: the least
time of one step (its FLOPs over the bf16 peak or its bytes over HBM
bandwidth, the bytes binding: every bf16 weight read once and each
slot's recurrent state read and written; ``flops.py``) times the steps
in the trace, over the device time of the engine's ``_decode_fn``
program. Named ``mfu`` because it is the whole step's share."""

from chipbench import flops, tracing

PROGRAM = "jit__decode_fn"


def read(view):
    evs = tracing.ops_named(view.trace, PROGRAM, lines="modules")
    if not evs:
        return None
    fl, nb = flops.rwkv_decode_step_cost(view.facts["model"], view.facts["slots"])
    t_min, _ = flops.roofline_seconds(fl, nb, view.peak)
    spent = sum(e - s for _, s, e in evs) / 1e9
    return 100.0 * len(evs) * t_min / spent
