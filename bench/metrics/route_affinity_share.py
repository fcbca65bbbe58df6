"""Gateway: the share of child stages (stages with a parent stage) that the
router placed on the node their parent ran on, where the parent's
transcript lies in the prefix index, from the nodes' own counters over the
window (``node_child_affine`` over ``node_child_stages``), in percent."""


def read(w):
    # a program without the routing counters reads nothing here
    if "node_child_stages" not in w.stats1[0]:
        return None
    stages = sum(b["node_child_stages"] - a["node_child_stages"]
                 for a, b in zip(w.stats0, w.stats1))
    affine = sum(b["node_child_affine"] - a["node_child_affine"]
                 for a, b in zip(w.stats0, w.stats1))
    return 100.0 * affine / stages if stages else None
