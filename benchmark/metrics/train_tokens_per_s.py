"""Tokens of the window's completed training steps over the window's
seconds (the trainer driver's count, ``Run.extra``)."""


def read(run):
    tokens = run.extra.get("tokens")
    if not tokens or run.window_s <= 0:
        return None
    return tokens / run.window_s
