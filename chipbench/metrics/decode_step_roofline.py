"""Share of the decode step's roofline, in percent: the least time the
chips could take for what the steps in the traced window needed (the
weights once, plus the K/V of live positions of active slots; FLOPs of
the active slots' tokens), over the serve step's mean device time."""
from chipbench import flops as F
from chipbench import trace as T
from chipbench.metrics._programs import SERVE_STEP


def read(obs):
    if obs.trace is None or obs.peaks is None:
        return None
    n, sec = T.module_seconds(obs.trace, SERVE_STEP)
    steps = obs.steps_in(*obs.trace_window)
    if not n or not steps:
        return None
    need = [F.roofline_seconds(*F.decode_step_need(obs.cfg, s.contexts),
                               obs.peaks, obs.chips) for s in steps]
    return 100.0 * (sum(need) / len(need)) / (sec / n)
