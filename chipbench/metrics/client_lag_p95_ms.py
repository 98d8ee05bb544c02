"""How late the client submitted: 95th percentile of submit time minus
due time over the requests due in the window."""
from chipbench.stats import pct


def read(obs):
    return pct([(t.submitted - t.due) * 1e3 for t in obs.due_in_window()], 95)
