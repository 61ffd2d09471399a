"""Kernels: least time over device busy time in the traced span, in %.

Least time = sum over the span's requests, over each query's terms, of
df(term) x 8 bytes (int32 doc id + int32 tf: the exact tier's own posting)
divided by the HBM bytes/s of the devices that share the work: n chips read
their shards side by side, so n x one chip's rate, n being the device planes
of the capture (`per_device_busy_s`). It is held against the planes' mean busy
time (`busy_s`). On one device n is 1 and the value what it always was. Bound:
bytes. It reads the same work whatever program scores it, and however many
chips share it. df comes from the generator's own arrays."""

POSTING_BYTES = 8


def least_seconds(df, queries, hbm_bytes_per_s: float, devices: int = 1) -> float:
    return (sum(int(df[t]) for q in queries for t in q) * POSTING_BYTES
            / (devices * hbm_bytes_per_s))


def read(run):
    if run.trace is None or not run.traced or not run.peak:
        return None
    busy = run.trace["busy_s"]
    if busy <= 0:
        return None
    df = run.df()
    queries = [run.pool[r.query] for r in run.traced if r.ok]
    devices = len(run.trace.get("per_device_busy_s", ())) or 1
    return 100.0 * least_seconds(df, queries, run.peak["hbm_bytes_per_s"],
                                 devices) / busy
