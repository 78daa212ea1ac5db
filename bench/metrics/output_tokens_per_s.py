"""Output tokens that came back inside the window, over the window."""


def read(run):
    return run.window_tokens / run.seconds
