from ..reduce_trace import kernel_events


def read(ev, kernel, **_):
    """Least time the chip could take for the kernel's work in the traced
    window (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s, a call) over the time its events took in the trace.
    kernels/<kernel>.py computes the work from shapes."""
    r, peaks = ev.get("reduced"), ev.get("peaks")
    if not r or not peaks:
        return None
    mod = ev["cell"].module("kernels", kernel)
    least = took = 0.0
    bounds = {}
    for name, (flops, nbytes) in mod.work(
            ev, lambda name: kernel_events(r, name)[1]).items():
        t = kernel_events(r, name)[0]
        if t <= 0.0:
            continue
        by_flops = flops / peaks["bf16_flops"]
        by_bytes = nbytes / peaks["hbm_bytes_per_s"]
        least += max(by_flops, by_bytes)
        took += t
        bounds[name] = {"bound": "compute" if by_flops >= by_bytes
                        else "memory", "least_s": max(by_flops, by_bytes),
                        "took_s": t, "share": 100 * max(by_flops, by_bytes) / t}
    if took <= 0.0:
        return None
    return {"value": 100.0 * least / took, "detail": bounds}
