"""Host seconds spent generating the cell's graph and its device features
(the benchmark's ``graph_build`` span)."""
LAYER = "set-up: graph on the host"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    return run["spans"].get("graph_build")
