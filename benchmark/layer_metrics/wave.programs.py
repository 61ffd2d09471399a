"""Device programs: wave programs the warm-up compiled: misses of the
wave-program caches when the window starts (`es.jit.cache.wave_program.misses`;
every miss builds one program of the bounded family). 0 where the front end is
off; nothing where the server ships no such counter."""

from benchlib import waves


def read(run):
    return run.before.get("counters", {}).get(waves.PROGRAM_MISSES)
