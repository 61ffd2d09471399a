"""Host planning, dispatch and fetch: milliseconds a search spends on the
bookkeeping of its measurements (span `engine.account`): the XLA cross-check
and counters beside its dispatch, `time_kernel`'s cost-model arithmetic after
its fetch, its slow log. 0 where no search paid for any (a served search's
wave keeps its own inside its stages); nothing where the server ships no such
counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, ["es.span.engine.account.ns"],
                      "es.span.rest.search.count", 1e-6)
