"""Tokens a scheduler step carries: (prompt tokens prefilled + tokens
decoded in the window, from the clients' frames) over the difference of
/statsz `chunked.steps` across the window."""


def read(obs):
    steps = obs.get("steps")
    work = obs.get("work")
    if not steps or not work:
        return None
    return (work["prefilled"] + work["decoded"]) / steps
