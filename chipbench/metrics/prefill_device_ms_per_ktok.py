"""Device time of the prefill programs in the trace (first device) per
thousand prompt tokens admitted while it was taken (true lengths, not
padded buckets)."""
from chipbench import trace as T
from chipbench.metrics._programs import PREFILL


def read(obs):
    if obs.trace is None:
        return None
    lo, hi = obs.trace_window
    toks = sum(len(t.req.prompt) for t in obs.tracks
               if t.admitted is not None and lo <= t.admitted < hi)
    n, sec = T.module_seconds(obs.trace, PREFILL)
    if not toks or not n:
        return None
    return sec * 1e3 / (toks / 1000.0)
