"""A counter's increase over the window, summed over the values `of` of
its label `label` and multiplied by `scale`, per completed operation of
kind `per`. Nothing when the program has none of those series (it does
not count this), or when no such operation completed."""


def reduce(w, counter: str, label: str, of: list, per: str,
           scale: float = 1.0):
    from dds_tpu.obs.metrics import metrics

    if all(metrics.value(counter, **{label: v}) is None for v in of):
        return None
    n = sum(1 for op in w.ops if op.kind == per and op.status == 200)
    total = sum(w.counter_delta(counter, **{label: v}) for v in of)
    return scale * total / n if n else None
