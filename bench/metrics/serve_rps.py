"""Requests scheduled in the window whose label was in hand before the
window closed, per second of window."""


def read(run):
    return run.window.done_in_window() / run.window.seconds
