"""Process start to the first POST of the window: imports, the server,
generation and staging of the scans, compiles or compile-cache loads,
the warm-up statements. The reference runs after the window and is not
in it."""


def read(run):
    return run["setup_s"]
