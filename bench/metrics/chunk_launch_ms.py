"""Model programs: the median ``engine.chunk`` span, from its argument
build to the return of its token sync: one chunked-prefill launch. Read
only from a window run with the program's spans on (``bench/spans.py``)."""
import statistics


def read(w):
    d = (w.trace or {}).get("span_durations", {}).get("engine.chunk")
    return 1e3 * statistics.median(d) if d else None
