"""Mean host milliseconds per step inside one of the benchmark's spans."""


def read(ctx, span):
    spans = ctx["spans"]
    n = spans.count.get(span)
    if not n:
        return None
    return spans.total_ns[span] / n / 1e6
