"""Median of the scheduler's own wait, from the program's request stamps:
``Request.admitted_at`` (given a slot) minus ``submitted_at`` (queued),
both ``time.perf_counter``, over the requests due in the window; one not
admitted by the window's end counts with the wait it had then. Nothing to
read from a program that does not stamp ``admitted_at``."""
from chipbench.stats import pct


def read(obs):
    due = [t for t in obs.due_in_window() if not t.refused]
    if not due or not all(hasattr(t.handle, "admitted_at") for t in due):
        return None
    waits = []
    for t in due:
        at = t.handle.admitted_at
        waits.append((min(obs.t_end if at is None else at, obs.t_end)
                      - t.handle.submitted_at) * 1e3)
    return pct(waits, 50)
