"""Output tokens given while the window's arrivals last, over the window's
length (host clock).  Tokens of the drain after it are not counted, so
where the longest requests happen to arrive does not move the rate."""


def read(ctx):
    raw = ctx["raw"]
    if "window_tokens" not in raw:
        return None
    return raw["window_tokens"] / raw["window_s"]
