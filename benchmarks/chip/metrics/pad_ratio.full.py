"""Edge slots in the packs the training step's plans use, over the
real edges those packs carry (one pass over the graph per pack)."""


def read(run):
    packs = run.layer.get("packs")
    if not packs:
        return None
    return sum(packs.values()) / (len(packs) * run.layer["n_edges"])
