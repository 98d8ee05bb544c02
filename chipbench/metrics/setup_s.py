"""Set-up seconds: process start to the window's opening (imports, plan,
weights, warm-up, compiles or cache loads)."""


def read(obs):
    return obs.setup_s
