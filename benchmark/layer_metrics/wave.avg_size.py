"""Serving front end: members a wave, over the waves that ended in the window
(`es.serving.wave.members` over `es.serving.wave.count`). 0 where no wave ended
(a cell without the front end); nothing where the server ships no such
counter."""

from benchlib import waves


def read(run):
    return waves.mean(run, [waves.MEMBERS], waves.WAVES)
