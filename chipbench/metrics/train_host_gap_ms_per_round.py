"""The host time per ADSP round in which the chip has no step queued:
the mean, over consecutive rounds k, k+1, of the time from the end of
round k's ``adsp.sync`` (the loss on the host, so the step has ended)
to the end of round k+1's ``adsp.dispatch`` (the next step queued);
spans of ``repro.cluster.mesh_backend``, keyed by round. None where the
program records no such span."""


def read(view):
    try:
        from repro.fleet.metrics import recorded_spans
    except ImportError:  # a program without the span recorder
        return None
    spans = recorded_spans()
    synced = {s.key: s.end_ns for s in spans if s.name == "adsp.sync"}
    queued = {s.key: s.end_ns for s in spans if s.name == "adsp.dispatch"}
    gaps = [queued[k + 1] - end for k, end in synced.items() if k + 1 in queued]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
