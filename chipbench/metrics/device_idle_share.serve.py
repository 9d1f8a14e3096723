"""Share of the traced serving window (arrivals, steps and the drain) in which no operation ran on the
device, averaged over the chips in use: 1 − (union of the device's
operation intervals) / window."""

from chipbench import tracing


def read(view):
    devs = sorted(view.trace.ops)[: view.chips]
    if not devs:
        return None
    busy = sum(tracing.busy_ns(view.trace, d) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / view.trace.window_ns)
