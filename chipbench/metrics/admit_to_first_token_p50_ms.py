"""Median time from the scheduler giving a request a slot to the engine
reading its first token back, from the program's request stamps
(``Request.first_token_at`` minus ``admitted_at``, ``time.perf_counter``):
the prefill, splice and admit programs, the first decode steps and the
retire that reads the token. Over the requests due in the window and
admitted before its end; one with no token by then counts with the wait
it had then. Nothing to read from a program without these stamps."""
from chipbench.stats import pct


def read(obs):
    due = [t for t in obs.due_in_window() if not t.refused]
    if not due or not all(hasattr(t.handle, "first_token_at") for t in due):
        return None
    waits = []
    for t in due:
        at, first = t.handle.admitted_at, t.handle.first_token_at
        if at is None or at >= obs.t_end:
            continue
        waits.append((min(obs.t_end if first is None else first, obs.t_end)
                      - at) * 1e3)
    return pct(waits, 50)
