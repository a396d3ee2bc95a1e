"""A counter's increase over the window per completed operation of kind
`per`."""


def reduce(w, counter: str, labels: dict, per: str):
    n = sum(1 for op in w.ops if op.kind == per and op.status == 200)
    return w.counter_delta(counter, **labels) / n if n else None
