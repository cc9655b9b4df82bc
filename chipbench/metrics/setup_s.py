"""Set-up seconds: from the process's start to the window's start (imports,
device start, warm-up and any compiling, loading, traffic generation)."""


def read(run):
    return run.setup_s
