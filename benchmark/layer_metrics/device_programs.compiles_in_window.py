"""Device programs: XLA compiles the server counted between the window's
first request and its last answer (`_nodes/stats` device.jit.compiles)."""


def read(run):
    return run.after["compiles"] - run.before["compiles"]
