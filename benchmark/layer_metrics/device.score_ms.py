"""Kernels: device milliseconds a request spends under the scope `score`
(`XLA Ops` whose op_name carries it: the query tree's `device_eval`), over
the requests sent and answered inside the capture. On several device planes
it is the first plane's time: what one of the chips that share a request
spends on it, not the sum over the chips."""

from benchlib import spans


def read(run):
    return spans.scope_ms_per_request(run, "score")
