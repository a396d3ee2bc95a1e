"""The fold program's share of its roofline, in %: the least time the chip
could take for one fold of the cell's K rows by its published peaks
(`work.py`, `peaks.json`), over the mean device time of one fold."""

from yardstick import work
from yardstick.reducers.trace_module_ms import device_ms_per_run


def reduce(w, line: str, pattern: str, column: int):
    dev = device_ms_per_run(w, line, pattern)
    if dev is None:
        return None
    least = work.fold_least_seconds(w.config["rows"], w.limbs[column],
                                    w.device_kind)
    return 100.0 * least["seconds"] * 1e3 / dev
