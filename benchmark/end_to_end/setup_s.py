"""Process start to the opening of the window: tokenizers, weights,
assembly, warm-up (compilation in a cold run), server, upload, lead-in."""


def read(ctx):
    return ctx["setup_s"]
