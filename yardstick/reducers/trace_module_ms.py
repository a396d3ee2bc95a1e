"""Mean device time, in ms, of one execution of the programs whose name
matches `pattern` on line `line` of the profiler trace."""

from yardstick import trace_reduce as tr


def device_ms_per_run(w, line: str, pattern: str):
    if w.trace is None:
        return None
    t = w.trace
    secs = sum(tr.op_seconds(t["planes"], line, t["lo_ns"], t["hi_ns"],
                             pattern).values())
    runs = tr.op_count(t["planes"], line, t["lo_ns"], t["hi_ns"], pattern)
    return 1e3 * secs / runs if runs and secs > 0 else None


def reduce(w, line: str, pattern: str):
    return device_ms_per_run(w, line, pattern)
