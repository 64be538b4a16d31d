"""Tokens decoded per decode step inside the measured window.

The tokens of the decode chunks harvested inside the window over the
steps those chunks ran (``decode_chunk`` each): the mean number of
slots that held a decoding request, from the harness's token stamps."""


def read(ctx):
    tokens, harvests = ctx["harvests"](*ctx["window"])
    steps = harvests * ctx["engine"]["decode_chunk"]
    return tokens / steps if steps else None
