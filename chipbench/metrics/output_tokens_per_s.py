"""Output tokens the client received inside the window, over the window's
seconds (host clock)."""


def read(obs):
    n = sum(1 for t in obs.tracks for s in t.tokens if obs.t0 <= s < obs.t_end)
    return n / obs.seconds
