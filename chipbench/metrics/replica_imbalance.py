"""The busiest replica's frames (engine runs in the window) over the
mean across replicas, from ``snapshot()["replicas"]``."""


def read(run):
    runs = [b["engine_runs"] - a["engine_runs"]
            for a, b in zip(run.start["replicas"], run.end["replicas"])]
    if not runs or sum(runs) <= 0:
        return None
    return max(runs) / (sum(runs) / len(runs))
