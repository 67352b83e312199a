"""Seconds from process start to window start (host clock)."""


def read(window):
    return window.setup_s
