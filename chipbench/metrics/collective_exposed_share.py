"""Share of the traced window in which a collective runs on the first
device and no other operation does, in percent."""
from chipbench import trace as T


def read(obs):
    if obs.trace is None or len(obs.trace.ops) < 2:
        return None
    lo, hi = obs.trace.window()
    return 100.0 * T.collective_exposed_seconds(obs.trace) / ((hi - lo) * 1e-9)
