"""Mean ``BPResult.rounds`` over the window's solves (program counter)."""


def read(o):
    if not o.solves:
        return None
    return sum(s["rounds"] for s in o.solves) / len(o.solves)
