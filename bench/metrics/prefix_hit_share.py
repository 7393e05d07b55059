"""prefix_hit_share: share of the window's prompt tokens that the prefix
cache served, from the session's own counters (``PrefixCache.stats``):
the change of ``hit_tokens`` over the change of ``prompt_tokens`` across
the window. Nothing to read where the session has no prefix cache."""


def read(run):
    c0, c1 = run.counters
    if "prompt_tokens" not in c1:
        return None
    prompt = c1["prompt_tokens"] - c0["prompt_tokens"]
    if prompt <= 0:
        return None
    return 100.0 * (c1["hit_tokens"] - c0["hit_tokens"]) / prompt
