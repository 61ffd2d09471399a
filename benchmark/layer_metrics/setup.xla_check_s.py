"""Device programs: seconds the XLA cross-check of the cost model
(`check_dispatch`: a program's lowering, compiling and analysing once more)
had taken when the window started (`es.costmodel.xla_check.ns`). Nothing
where the server ships no such counter."""


def read(run):
    ns = run.before.get("counters", {}).get("es.costmodel.xla_check.ns")
    return None if ns is None else ns / 1e9
