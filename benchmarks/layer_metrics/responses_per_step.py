"""``hvd_responses_dispatched_total`` over the window, per step: how
many fused responses the eager plane executed for one step's
gradients."""

LAYER = "Eager plane"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "samples_per_s_chip"


def read(run: dict):
    dispatched = run.get("responses_dispatched")
    if dispatched is None or not run.get("steps"):
        return None
    return dispatched / run["steps"]
