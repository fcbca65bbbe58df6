"""Engine: the chip's idle time whose innermost span is one of the
engine's (``engine.*``: admission, prefix lookup, page pre-grant, the
launches up to and inside their token syncs, publication, release), over
the traced window, on the chip that ``device_idle_share`` reports. Read
only from a window run with the program's spans on (``bench/spans.py``)."""


def read(w):
    idle = (w.trace or {}).get("idle_by_span")
    if idle is None:
        return None
    return 100.0 * sum(s for name, s in idle.items()
                       if name.startswith("engine.")) / w.trace["window_s"]
