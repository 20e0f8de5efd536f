"""replay_products_per_s: scalar products of every plan-hit multiply the
window completed, over the window's seconds (host clock)."""


def read(ctx):
    w = ctx.window
    if w.loop != "replay":
        return None
    return float(w.products.sum()) / w.window_s
