"""Mean share of slots holding a request after each ``step()`` in the
window, in percent."""


def read(obs):
    steps = obs.steps_in(obs.t0, obs.t_end)
    if not steps:
        return None
    return 100.0 * sum(s.active for s in steps) / (len(steps) * obs.slots)
