"""The fused int8 decode + commit apply kernel's share of its roofline.
It is memory-bound: per round one call per parameter leaf, each moving
the bytes of ``flops.int8_decode_apply_bytes``; the least time of a
round's calls is their bytes over HBM bandwidth. The share is that
times the rounds in the trace (calls / leaves) over the device time of
the calls."""

from chipbench import flops, tracing


def read(view):
    evs = tracing.ops_named(view.trace, "int8_decode_apply")
    if not evs:
        return None
    sizes = flops.param_leaf_sizes(view.facts["model"])
    per_round = sum(flops.int8_decode_apply_bytes(n) for n in sizes) / view.peak["hbm_bytes_per_s"]
    spent = sum(e - s for _, s, e in evs) / 1e9
    return 100.0 * (len(evs) / len(sizes)) * per_round / spent
