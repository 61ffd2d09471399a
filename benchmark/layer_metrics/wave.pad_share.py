"""Device programs: the share of the rows the window's wave programs computed
that belonged to no request, in per cent: 1 - members over padded rows
(`es.serving.wave.padded_rows`: a term lane's batch tier, the power of two its
members were padded to; one row for any other member). 0 where no wave ended;
nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    real = waves.mean(run, [waves.MEMBERS], waves.ROWS)
    if real is None:
        return None
    return 100.0 * (1.0 - real) if waves.added(run, waves.ROWS) else 0.0
