"""Reader ``trace_idle_ms``: time of the traced slice in which NO device op ran,
per call (the kind's counter ``slice_calls``), in milliseconds: what the host
spends around each call while the device waits. Averaged over the chips used.
Nothing to read (no trace, no call) gives nothing.
"""


def read(args: dict, run: dict):
    sl = run.get("slice")
    calls = run.get("counters", {}).get("slice_calls")
    if not sl or not calls:
        return None
    return 1e3 * (sl["window_s"] - sl["busy_s"]) / calls
