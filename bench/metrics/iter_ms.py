"""Engine: the traced slice's length over the engine steps in it (mean over
replicas), milliseconds."""


def read(run):
    tr = run.trace
    if not tr or not tr["steps"]:
        return None
    per_replica = len(tr["steps"]) / len({r for r, _ in tr["steps"]})
    return tr["window_s"] / per_replica * 1e3
