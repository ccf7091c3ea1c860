"""setup_s: seconds from the process start to the window's open:
imports, weights, compiles or cache loads, warm-up, pre-roll."""


def read(obs):
    return obs["setup_s"]
