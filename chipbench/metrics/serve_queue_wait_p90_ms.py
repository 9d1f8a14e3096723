"""The 90th percentile, over the requests offered in the traced window,
of the time each waited in the engine's admission queue: from
``submit`` to the scheduler handing it a slot, the engine's
``serve.admit`` spans (``repro.fleet.metrics``). A request offered and
never admitted ranks last. The 90th and not the 95th: the traced
stretch offers about 120 requests, so at least 10 lie beyond it. None
where the program records no such span."""

import math

from chipbench import traffic_gen


def read(view):
    try:
        from repro.fleet.metrics import recorded_spans
    except ImportError:  # a program without the span recorder
        return None
    waits = [s.duration_ns / 1e6 for s in recorded_spans() if s.name == "serve.admit"]
    if not waits:
        return None
    offered = max(view.facts.get("requests", 0), len(waits))
    return traffic_gen.window_quantile(waits + [math.inf] * (offered - len(waits)), 0.9)
