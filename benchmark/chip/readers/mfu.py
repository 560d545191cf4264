"""The whole step's share of the chips' peak: model FLOPs per sample (from
shapes, recompute not counted) x samples/s of the window, over chips x peak."""


def read(ctx):
    m = ctx["measured"]
    if ctx["peaks"] is None or not m.get("flops"):
        return None
    return 100.0 * m["flops"] / (m["seconds"] * ctx["chips"] * ctx["peaks"]["flops"])
