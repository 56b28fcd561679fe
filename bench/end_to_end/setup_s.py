"""Process start to the first due request: imports, calibration of the
planner's cost model, weights, and the warm-up of every shape (compiles or
loads from the persistent cache included)."""


def read(served):
    return served.setup_s
