"""Helpers the decode readers share: the served tokens of the window."""


def depths(ctx):
    """Cached positions each token served inside the traced part of the
    window attended over: the prompt, the re-fed last prompt token, and
    the tokens before it."""
    t0, t1 = ctx["traced"]
    out = []
    for r in ctx["requests"]:
        P = len(r.prompt)
        out.extend(P + 1 + i for i, t in enumerate(r.tokens.times)
                   if t0 <= t < t1)
    return out
