"""Device programs: seconds of XLA compilation the server had counted when
the window started (`_nodes/stats` device.jit.compile_time_in_millis);
persistent-cache hits count with the little time they take."""


def read(run):
    return run.setup.get("compile_s")
