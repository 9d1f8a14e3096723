"""The whole ADSP step's share of the chips' bf16 peak: useful model
FLOPs per committed token (6·N plus causal attention, ``flops.py``;
masked microsteps commit no token and count for nothing) times the
committed tokens per second of the traced window, over chips × peak."""


def read(view):
    f = view.facts
    if not f.get("committed_tokens"):
        return None
    return 100.0 * f["tokens_per_s"] * f["flops_per_token"] / (
        view.chips * view.peak["bf16_flops_per_s"])
