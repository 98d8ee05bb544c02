"""95th percentile of every gap between two consecutive tokens of one
request, both received inside the window (host clock)."""
from chipbench.stats import pct


def read(obs):
    gaps = []
    for t in obs.tracks:
        s = [x for x in t.tokens if obs.t0 <= x < obs.t_end]
        gaps += [(b - a) * 1e3 for a, b in zip(s, s[1:])]
    return pct(gaps, 95)
