from ..arithmetic import percentile, step_intervals


def read(ev, q, **_):
    """Percentile `q` of the gaps between step completions in the window:
    the median says what a step costs when nothing is in its way, the 95th
    whether stalls sit on the steps that `train_tokens_per_s` counts."""
    if "stamps" not in ev:
        return None
    gaps = step_intervals(ev["stamps"], ev["t0"], ev["t1"])
    return 1e3 * percentile(gaps, q) if gaps else None
