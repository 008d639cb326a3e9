"""Process start to the window's first edge: keys, boot (the corpus is
signed beside it), sender start, warm-up."""


def read(ctx):
    return ctx["setup_s"]
