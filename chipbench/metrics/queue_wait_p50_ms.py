"""Median wait from due time to the first ``step()`` after which the
scheduler holds the request in a slot; one not admitted by the window's
end counts with the wait it had then."""
from chipbench.stats import pct


def read(obs):
    waits = [(min(t.admitted if t.admitted is not None else obs.t_end,
                  obs.t_end) - t.due) * 1e3
             for t in obs.due_in_window() if not t.refused]
    return pct(waits, 50)
