"""A number the harness took on its own clock during set-up."""


def reduce(w, key: str):
    return w.setup.get(key)
