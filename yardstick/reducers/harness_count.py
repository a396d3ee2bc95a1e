"""A count the harness made itself over the window (`Window.counts`)."""


def reduce(w, key: str):
    return w.counts.get(key)
