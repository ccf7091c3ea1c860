"""train_tok_s: tokens of every train step that ended in the window,
over the window (the last step waited for)."""


def read(obs):
    if "tokens_per_step" not in obs:
        return None
    return obs["steps"] * obs["tokens_per_step"] / obs["window_s"]
