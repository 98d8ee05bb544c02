"""Share of the traced window with no operation on the device, averaged
over the chips used, in percent."""
from chipbench import trace as T


def read(obs):
    if obs.trace is None or not obs.trace.ops:
        return None
    lo, hi = obs.trace.window()
    return 100.0 * (1.0 - T.busy_seconds(obs.trace) / ((hi - lo) * 1e-9))
