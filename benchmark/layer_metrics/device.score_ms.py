"""Kernels: device milliseconds a request spends under the scope `score`
(`XLA Ops` whose op_name carries it: the query tree's `device_eval`), over
the requests sent and answered inside the capture."""

from benchlib import spans


def read(run):
    return spans.scope_ms_per_request(run, "score")
