"""Process start to the window's start: imports, data from the seed, first
calls (compile, or tracing and loading the cached executable)."""


def read(win):
    return win.setup_s
