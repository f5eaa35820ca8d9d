"""Share of real directed edges committed per round, over the window's
solves: ``BPResult.updates`` over real edges times rounds (program
counters). The paper's parallelism lever: rnbp commits only unconverged
edges, each with probability p."""


def read(o):
    rounds = sum(s["rounds"] for s in o.solves)
    if not rounds:
        return None
    updates = sum(s["updates"] for s in o.solves)
    return 100.0 * updates / (o.n_real_edges * rounds)
