"""Device time of the activity step (synaptic input and neuron update: the
scan body outside the connectivity-update branch), per step."""


def read(reduced: dict, ctx: dict):
    busy = sum(e[2] for e in reduced["events"] if e[3] == "activity")
    return busy * 1e3 / ctx["steps"] if busy > 0 else None
