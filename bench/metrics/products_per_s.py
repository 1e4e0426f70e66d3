"""Coded products completed in the window, over the window's length."""


def read(run):
    return len(run.ok_calls) / run.window_s
