def read(ev, **_):
    """Lowerings (new signatures of any jitted function) inside the window,
    from jax's monitoring events; the capture tier's own count rides in the
    run's `correct`.  Expected 0."""
    c = ev.get("counters")
    return None if c is None else float(c["jax_lowerings"])
