"""Slot-steps that carried a request (each request holds its slot for
prompt + output - 1 steps) over steps times slots."""


def read(ctx):
    raw = ctx["raw"]
    if "totals" not in raw or not raw["steps"]:
        return None
    return 100.0 * raw["totals"]["slot_steps"] / (
        raw["steps"] * raw["max_batch"])
