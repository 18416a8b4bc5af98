def read(ev, **_):
    peak = ev["device"].get("memory_peak_bytes", 0)
    return peak / 1e9 if peak else None
