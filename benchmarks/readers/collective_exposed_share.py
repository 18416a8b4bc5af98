def read(ev, **_):
    """Time a collective runs and no compute does, on the worst device, as
    a share of the traced window."""
    r = ev.get("reduced")
    if not r or r["devices"] < 2:
        return None
    return {"value": 100.0 * r["collective_exposed_share_worst"],
            "detail": {"collective_share_worst":
                       100.0 * r["collective_share_worst"]}}
