"""Write path (index/pack.py, analysis/, index/device_build.py): documents
per second of set-up's one `_refresh`, harness clock."""


def read(run):
    s = run.setup
    return s["docs"] / s["refresh_s"] if s.get("refresh_s") else None
