"""Kernels: device milliseconds a request spends under the scope `topk`
(`XLA Ops` whose op_name carries it: the streamed Pallas top-k and the global
merge), over the requests sent and answered inside the capture."""

from benchlib import spans


def read(run):
    return spans.scope_ms_per_request(run, "topk")
