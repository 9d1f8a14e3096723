"""Host milliseconds per ADSP round spent in the control plane: the self
time of the window's ``adsp.control`` spans (τ_i, the engine's commit
handling, checkpoints and epoch ends; a span's duration less the part
its child spans cover, so probe rounds of a search count as rounds and
not as control) over the window's ``adsp.round`` spans. None where the
program records no such span."""


def read(view):
    try:
        from repro.fleet.metrics import recorded_spans
    except ImportError:  # a program without the span recorder
        return None
    spans = recorded_spans()
    rounds = sum(s.name == "adsp.round" for s in spans)
    control = {s.id: s for s in spans if s.name == "adsp.control"}
    if not rounds or not control:
        return None
    own = {i: s.end_ns - s.start_ns for i, s in control.items()}
    for c in spans:
        p = control.get(c.parent)
        if p is not None:
            own[p.id] -= max(min(c.end_ns, p.end_ns) - max(c.start_ns, p.start_ns), 0)
    return sum(own.values()) / rounds / 1e6
