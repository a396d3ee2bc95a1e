"""Mean duration, in ms, of the program's spans of one name."""


def reduce(w, span: str):
    durs = [s.dur_ms for s in w.spans if s.name == span]
    return sum(durs) / len(durs) if durs else None
