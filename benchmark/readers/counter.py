"""Reader ``counter``: a ratio of two numbers the kind collected from the
program's own counters, taken from outside over the measured window.

args: {"num": <key>, "den": <key>} — keys of the run's ``counters`` dict. A
counter that the kind did not collect gives nothing.
"""


def read(args: dict, run: dict):
    counters = run.get("counters", {})
    num, den = counters.get(args["num"]), counters.get(args["den"])
    if num is None or not den:
        return None
    return num / den
