"""Sampled tokens DELIVERED inside the window (stamped by the client, of
requests that went on to complete correctly), over the window. Counting
tokens and not whole requests keeps the window's edges from quantising the
result by a request's length. tokens/s, higher is better."""


def read(run):
    if run.window_s <= 0:
        return None
    n = sum(1 for r in run.records if r["ok"]
            for t in r["stamps"] if 0 <= t <= run.window_s)
    return n / run.window_s if n else None
