"""What Python's collector took of the traced window, in ms a step: the
program's `gc_pause` accounts (full collections, and younger ones of 1 ms
or more) inside `facts["trace"]` `t0`..`t1`, over its steps.
Source: program_counter."""
from benchmark.lib.host_accounts import accounts, seconds


def read(ctx):
    trace = ctx["facts"].get("trace") or {}
    if "t0" not in trace or "t1" not in trace or not trace.get("steps"):
        return None
    pauses = accounts("gc_pause", trace["t0"], trace["t1"])
    return None if pauses is None else seconds(pauses) * 1e3 / trace["steps"]
