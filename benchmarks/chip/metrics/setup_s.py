"""setup_s: seconds from process start to the first timed query's due
time: JAX start-up, data generation, index build and upload, warm-up
(compilation or persistent-cache loads) -- host clock."""


def read(run):
    return run.setup_s
