"""The share of the grouped product's rows that hold an assignment: the
assignments the step counted on held experts over the rows of the blocks in
use (padding included), both the step's own counters, mean over the window's
steps. What experts_mfu_pct cannot see: its operations count real rows, the
time goes by blocks."""


def read(run):
    counted = run.get("counters_per_step", {})
    held, rows = counted.get("held_assignments"), counted.get("block_rows")
    if held is None or not rows:
        return None
    return 100.0 * held / rows
