"""Tokens of the optimizer steps completed in the window, over the window's
seconds (its start to the end of its last step), over the chips."""


def read(run):
    if "steps" not in run:
        return None
    return (len(run["steps"]) * run["tokens_per_step"]
            / run["window_s"] / run["chips"])
