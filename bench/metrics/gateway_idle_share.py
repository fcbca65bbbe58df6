"""Gateway: the chip's idle time whose innermost span is one of the
gateway's (``gateway.*``: refresh, dispatch, release, collect, finish,
sleep, or the step itself), over the traced window, on the chip that
``device_idle_share`` reports. Read only from a window run with the
program's spans on (``bench/spans.py``)."""


def read(w):
    idle = (w.trace or {}).get("idle_by_span")
    if idle is None:
        return None
    return 100.0 * sum(s for name, s in idle.items()
                       if name.startswith("gateway.")) / w.trace["window_s"]
