"""One label value's share, in %, of a counter's increase over the
window, among the label values listed in `of`."""


def reduce(w, counter: str, label: str, value: str, of: list):
    parts = {v: w.counter_delta(counter, **{label: v}) for v in of}
    total = sum(parts.values())
    return 100.0 * parts[value] / total if total > 0 else None
