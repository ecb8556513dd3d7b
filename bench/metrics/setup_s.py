"""Set-up time: from process start to the window's first scheduled
arrival. Loading, making the model from the seed, compiling (or reading
the persistent compile cache) and the service's warmup."""


def read(run):
    return run.setup_s
