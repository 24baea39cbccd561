"""Images whose logits reached the host inside the window, over the
window's length."""


def read(run):
    w = run.window
    n = sum(1 for ts in w.token_times.values() for t in ts
            if w.t0 <= t < w.t1)
    return n / w.seconds if n else None
