"""Device time of the connectivity update (the scan body's update branch:
deletion, vacancies, upward pass, descent, leaf resolve, acceptance,
commit), per update."""


def read(reduced: dict, ctx: dict):
    busy = sum(e[2] for e in reduced["events"] if e[3] == "update")
    return busy * 1e3 / ctx["updates"] if busy > 0 else None
