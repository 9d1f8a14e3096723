"""Device milliseconds of monolithic prefill per 1,000 prompt tokens: the
device time of the engine's prefill programs in the window (the
``jit_prefill_bucket`` events of the ``XLA Modules`` line) over the
valid prompt tokens of the window's ``serve.prefill`` spans
(``repro.fleet.metrics``). A prompt padded to its power-of-two bucket
pays for its padding here. None where either is missing."""

from chipbench import tracing

PROGRAM = "jit_prefill_bucket"


def read(view):
    try:
        from repro.fleet.metrics import recorded_spans
    except ImportError:  # a program without the span recorder
        return None
    evs = tracing.ops_named(view.trace, PROGRAM, lines="modules")
    tokens = sum(s.counts.get("valid", 0) for s in recorded_spans()
                 if s.name == "serve.prefill")
    if not evs or not tokens:
        return None
    spent_ms = sum(e - s for _, s, e in evs) / 1e6
    return spent_ms / (tokens / 1000)
