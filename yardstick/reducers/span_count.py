"""How many spans in the window match `pattern`. Reads 0, not nothing,
when none does: a count of none is the reading that is hoped for."""

import re


def reduce(w, pattern: str):
    rx = re.compile(pattern)
    return float(sum(1 for s in w.spans if rx.search(s.name)))
