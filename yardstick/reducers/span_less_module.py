"""Mean of span `span` inside the traced stretch of the window, less the
mean device time of the program it dispatches (`pattern` on `line`): what
the host adds around the device's work, in ms."""

from yardstick.reducers.trace_module_ms import device_ms_per_run


def reduce(w, span: str, line: str, pattern: str):
    dev = device_ms_per_run(w, line, pattern)
    if dev is None:
        return None
    lo, hi = w.trace["lo_pc"], w.trace["hi_pc"]
    durs = [s.dur_ms for s in w.spans
            if s.name == span and lo <= s.t_start and s.t_end <= hi]
    return sum(durs) / len(durs) - dev if durs else None
