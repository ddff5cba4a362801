"""qps: queries answered OK (on the device path, not degraded to the
host twin) of those sent in the window, over the time from its start to
the last answer (host clock).  The clients send nothing after the close
and the run waits for what they sent, so every answer counts over all
the time it took, and the rate does not step by whole ticks."""


def read(run):
    s = run.summary()
    return s["ok"] / (s["last"] - run.start)
