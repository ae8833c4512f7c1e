"""decode_tok_s: output tokens stamped inside the window over its length."""


def read(run):
    return run.tokens() / run.window_s
