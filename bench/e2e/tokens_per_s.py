"""Output tokens produced in the window over the window's seconds."""


def read(w):
    return w.tokens / w.seconds if w.seconds > 0 else None
