"""Share of admitted prompt tokens that the prefix cache served, in %.

``prompt_tokens_saved / prompt_tokens`` of the engine's prefix-cache
counters over the whole measured run."""


def read(ctx):
    pc = ctx["results"]["prefix_cache"]
    if not pc["enabled"] or not pc["prompt_tokens"]:
        return None
    return 100.0 * pc["prompt_tokens_saved"] / pc["prompt_tokens"]
