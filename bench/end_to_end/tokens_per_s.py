"""Output tokens of all residents that reached the host inside the window,
over the window's seconds."""


def read(served):
    return served.tokens_in_window() / served.window_s
