def read(ev, **_):
    r = ev.get("reduced")
    return None if not r else 100.0 * r["idle_share"]
