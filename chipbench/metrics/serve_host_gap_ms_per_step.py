"""The host time per engine action in which the chip has no program
queued: the mean, over consecutive actions k, k+1 (``serve.step``
spans) after which the engine still had work, of the time from the end
of k's last ``serve.fetch`` (its token ids on the host) to the end of
k+1's first ``serve.dispatch`` (its program queued). Pairs across an
empty engine, where the driver waits for the next arrival, are left
out. None where the program records no such span."""


def read(view):
    try:
        from repro.fleet.metrics import recorded_spans
    except ImportError:  # a program without the span recorder
        return None
    spans = recorded_spans()
    fetched, queued = {}, {}
    for s in spans:
        if s.name == "serve.fetch":
            fetched[s.parent] = max(fetched.get(s.parent, s.end_ns), s.end_ns)
        elif s.name == "serve.dispatch":
            queued[s.parent] = min(queued.get(s.parent, s.end_ns), s.end_ns)
    steps = sorted((s for s in spans if s.name == "serve.step"), key=lambda s: s.start_ns)
    gaps = [queued[b.id] - fetched[a.id] for a, b in zip(steps, steps[1:])
            if a.counts.get("has_work") and a.id in fetched and b.id in queued]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
