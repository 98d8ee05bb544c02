"""Time to first token of every request due in the window, from its due
time; a request with no token by the window's end counts with the wait it
had then."""


def ttfts_ms(obs):
    out = []
    for t in obs.due_in_window():
        if t.refused:
            continue
        first = t.tokens[0] if t.tokens else obs.t_end
        out.append((min(first, obs.t_end) - t.due) * 1e3)
    return out
