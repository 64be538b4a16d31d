"""Device time of chunked prefill per 1000 prompt tokens computed, in ms.

The traced device time of one execution of the programs that run the
paged prefill kernel (one per prefill chunk), over the prompt tokens a
chunk computed on average in the run: the prompt tokens the prefix
cache did not serve, over the chunks run (``ServeStats``).  A chunk
that is only partly filled costs its whole time and counts only its
tokens."""

from bench import trace

PREFILL_KERNEL = "paged_prefill_attention"


def read(ctx):
    secs, n = trace.programs(ctx["trace"], running=PREFILL_KERNEL)
    r = ctx["results"]
    pc = r["prefix_cache"]
    computed = pc["prompt_tokens"] - pc["prompt_tokens_saved"]
    if not n or not r["prefill_chunks"] or not computed:
        return None
    per_chunk = computed / r["prefill_chunks"]
    return 1e6 * (secs / n) / per_chunk
