"""Output tokens delivered to clients inside the window, per second."""


def read(run):
    n = run.facts.get("tokens")
    return n / run.window_s if n else None
