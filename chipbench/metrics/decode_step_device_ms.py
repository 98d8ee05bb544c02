"""Mean device time of one execution of the fused serve step, from the
trace (first device)."""
from chipbench import trace as T
from chipbench.metrics._programs import SERVE_STEP


def read(obs):
    if obs.trace is None:
        return None
    n, sec = T.module_seconds(obs.trace, SERVE_STEP)
    return sec / n * 1e3 if n else None
