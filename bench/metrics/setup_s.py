"""setup_s: seconds from process start to the window: data, host encoding,
compile and cache loads, warm-up (host clock)."""


def read(run):
    return run.setup_s
