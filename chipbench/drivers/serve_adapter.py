"""What the serving driver needs of ``repro.serve.ServeEngine`` beyond its
public stepping API (``submit``, ``run_until``, ``has_work``): one timed
action at a time, and the tokens each request holds so far. Both read
the engine's private state; an engine hook that reported each token and
each admission on the host clock would replace this file.
"""

from __future__ import annotations

__all__ = ["step_once", "token_lists"]


def step_once(engine) -> None:
    """One prefill, chunk or decode step: ``run_until`` stops once the
    engine's virtual clock passes the bound, and every action advances
    it by more than this."""
    engine.run_until(engine.t + 1e-9)


def token_lists(engine) -> dict:
    """request id -> the tokens it has received, finished or not."""
    out = dict(engine._tokens_by_rid)
    for st in engine._slots.values():
        out[st.req.rid] = st.tokens
    return out
