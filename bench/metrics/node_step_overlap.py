"""Gateway: how many nodes were inside a step at once, on average over the
window: the nodes' wall seconds inside ``NodeRuntime.step``
(``node_step_s``), summed over the nodes and divided by the window. Nodes
stepped one after another read about 1 at most; n nodes stepped together
read up to n."""


def read(w):
    # a program without the counter reads nothing here
    if "node_step_s" not in w.stats1[0]:
        return None
    return sum(b["node_step_s"] - a["node_step_s"]
               for a, b in zip(w.stats0, w.stats1)) / w.seconds
