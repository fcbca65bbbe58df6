"""Model programs: the median ``engine.horizon`` span, from its argument
build to the return of its token sync: what a decoding stage waits for
each horizon of tokens. Read only from a window run with the program's
spans on (``bench/spans.py``)."""
import statistics


def read(w):
    d = (w.trace or {}).get("span_durations", {}).get("engine.horizon")
    return 1e3 * statistics.median(d) if d else None
