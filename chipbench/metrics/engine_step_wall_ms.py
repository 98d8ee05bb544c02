"""The window's seconds over the ``step()`` calls that returned in it:
the host loop's pace, time outside ``step()`` included."""


def read(obs):
    n = len(obs.steps_in(obs.t0, obs.t_end))
    return obs.seconds * 1e3 / n if n else None
