"""KV arena: KV bytes the admission holds for the running requests (the
node accountant's KV charge: every page granted at admission, for the
prompt and the predicted output) over the KV bytes those requests have
written into their pages, summed over the samples taken after every node
step of the window. Reserved memory that holds no KV keeps other requests
out."""


def read(w):
    res = sum(r for _, r, _ in w.rec.kv)
    written = sum(x for _, _, x in w.rec.kv)
    return res / written if written else None
